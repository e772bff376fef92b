"""A cell on four chips, rehearsed on four virtual CPU devices in a process of
its own: the harness builds the program's data mesh, places the flat
optimizer state in row shards and the batches split by rows over all four
devices, the program takes its GSNR statistics over the devices
(``gsnr_source`` "data_axis"), and a whole run of the tiny cell is correct
under the four-chip cell's limits.  The same run with the timed path broken
underneath is not: a step that returns its state unchanged, half of the
batch left out, and the exchange of the statistics between the chips left
out (each chip keeps its own)."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
HERE = pathlib.Path(__file__).resolve().parent
CELL = "bert-large.p1-dp4"
SEED = 2**31 + 57

SCRIPT = r"""
import contextlib, dataclasses, json, pathlib, sys
from unittest import mock

root, repo, seed = pathlib.Path(sys.argv[1]), pathlib.Path(sys.argv[2]), int(sys.argv[3])
sys.path[:0] = [str(repo), str(repo / "src")]
import jax

from benchmarks.chip import program, reference, spec
from benchmarks.chip import run as harness
from repro.backend import Backend
from repro.train import trainer

real_config, real_stats = program.train_config, trainer.device_grad_stats_fn
built = []


def fused(conf, traffic):
    # the chip's plan: the flat state and the Pallas kernels (interpreted here)
    cfg = real_config(conf, traffic)
    return cfg.replace(parallel=dataclasses.replace(cfg.parallel, backend=Backend.all_fused()))


def device_stats(*args, **kwargs):
    built.append(dict(mesh=dict(args[1].shape)))
    return real_stats(*args, **kwargs)


def unchanged_state(step):
    return lambda state, batch: (state, step(state, batch)[1])


def half_batch(step):
    return lambda state, batch: step(state, reference.half_batch(batch))


def no_exchange():
    return mock.patch.object(jax.lax, "pmean", lambda x, axis_name, **kw: x)


FAULTS = {"sound": (None, None), "unchanged_state": (unchanged_state, None),
          "half_batch": (half_batch, None), "no_exchange": (None, no_exchange)}
out = {"devices": len(jax.devices())}
with mock.patch.object(program, "train_config", fused), \
        mock.patch.object(trainer, "device_grad_stats_fn", device_stats):
    cell = spec.load_cell(root, "tiny.dp4")
    su = harness.prepare(cell, seed)
    m = su.state.opt_state["m"].data
    batch = next(su.it)
    out["m"] = [m.shape[0], sorted((s.device.id, s.data.shape[0]) for s in m.addressable_shards)]
    out["batch"] = {k: [v.shape[0], sorted((s.device.id, s.data.shape[0])
                                           for s in v.addressable_shards)]
                    for k, v in batch.items()}
    su.it.close()
    del su, m, batch
    for name, (wrap, patch) in FAULTS.items():
        with patch() if patch else contextlib.nullcontext():
            res = harness.run_cell(root, "tiny.dp4", seed, 0.2, False, require_chip=False,
                                   wrap_step=wrap)[0]
        out[name] = {"correct": res["correct"], "checks": res["checks"],
                     "count": res["device"]["count"]}
out["built"] = built
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def mesh_runs(tmp_path_factory):
    root = tmp_path_factory.mktemp("dp4")
    bench = root / "bench"
    for d in ("configs", "traffic", "limits"):
        (bench / d).mkdir(parents=True)
    for d in ("metrics", "families"):
        shutil.copytree(HERE / d, bench / d, ignore=shutil.ignore_patterns("__pycache__"))
    conf = {"name": "tiny", "source": "test", "hidden_size": 64, "num_hidden_layers": 2,
            "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
            "intermediate_size": 128, "vocab_size": 256, "hidden_act": "gelu_tanh",
            "mlp": "dense", "norm": "layernorm", "norm_eps": 1e-6, "causal": False,
            "rope_theta": 10000.0, "tie_word_embeddings": True, "param_dtype": "float32",
            "compute_dtype": "float32", "reduced": []}
    (bench / "configs" / "tiny.json").write_text(json.dumps(conf))
    tr = json.loads((HERE / "traffic" / "p1-dp4.json").read_text())
    tr.update(seq_len=32, rows=16, corpus_tokens=20000, trace_steps=2)
    tr["docs"].update(median=16, min=2, max=128)
    (bench / "traffic" / "tiny.json").write_text(json.dumps(tr))
    shutil.copy(HERE / "limits" / f"{CELL}.json", bench / "limits" / "tiny.dp4.json")
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    b.update(paths=["bench"],
             configs=[{"name": "tiny", "source": "test", "file": "bench/configs/tiny.json",
                       "reduced": [], "why": "test"}],
             workloads=[{"name": "tiny.dp4", "config": "tiny", "traffic": "tiny",
                         "chips": 4, "why": "test"}])
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", SCRIPT, str(root), str(ROOT), str(SEED)],
                       cwd=root, env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_the_traffic_asks_for_the_data_axis_over_the_cells_chips():
    tr = json.loads((HERE / "traffic" / "p1-dp4.json").read_text())
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    chips = {w["name"]: w["chips"] for w in bench["workloads"]}[CELL]
    assert tr["optimizer"]["gsnr_source"] == "data_axis"
    assert tr["k"] == chips == 4 and tr["rows"] % chips == 0


def test_state_and_batches_are_split_over_the_four_devices(mesh_runs):
    assert mesh_runs["devices"] == 4
    rows, shards = mesh_runs["m"]
    assert shards == [[d, rows // 4] for d in range(4)]
    for name, (n, parts) in mesh_runs["batch"].items():
        assert parts == [[d, n // 4] for d in range(4)], name


def test_the_statistics_are_taken_over_the_devices(mesh_runs):
    assert mesh_runs["built"] and all(b["mesh"] == {"data": 4} for b in mesh_runs["built"])


@pytest.mark.parametrize("fault", ["sound", "unchanged_state", "half_batch", "no_exchange"])
def test_a_sound_run_is_correct_and_each_fault_is_not(mesh_runs, fault):
    run = mesh_runs[fault]
    assert run["count"] == 4
    assert run["correct"] is (fault == "sound"), run["checks"]
