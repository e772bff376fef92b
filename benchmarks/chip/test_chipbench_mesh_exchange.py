"""The four-chip cell's checks see the exchange of the GSNR statistics.

The program reduce-scatters g and g² into the flat state's row shards
(``jax.lax.psum_scatter``) and all-reduces the loss (``jax.lax.pmean``).  On
four virtual CPU devices, in a process of its own, the tiny four-chip cell
runs with these collectives left out: each returns the chip's own input
(the scatter, its own rows of it).  Left out for the statistics alone or
for the loss too, the run is not correct, and the checks of the statistics
themselves, ``grad`` and ``gsnr``, fail."""
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
SEED = 2**31 + 91

SCRIPT = r"""
import dataclasses, json, pathlib, sys
from unittest import mock

root, repo, seed = pathlib.Path(sys.argv[1]), pathlib.Path(sys.argv[2]), int(sys.argv[3])
sys.path[:0] = [str(repo), str(repo / "src")]
import jax

from benchmarks.chip import program
from benchmarks.chip import run as harness
from repro.backend import Backend

real_config = program.train_config


def fused(conf, traffic):
    # the chip's plan: the flat state and the Pallas kernels (interpreted here)
    cfg = real_config(conf, traffic)
    return cfg.replace(parallel=dataclasses.replace(cfg.parallel, backend=Backend.all_fused()))


def own_rows(x, axis_name, *, scatter_dimension=0, axis_index_groups=None, tiled=False):
    assert tiled and axis_index_groups is None
    n = x.shape[scatter_dimension] // jax.lax.axis_size(axis_name)
    i = jax.lax.axis_index(axis_name)
    return jax.lax.dynamic_slice_in_dim(x, i * n, n, scatter_dimension)


def own_value(x, axis_name, **kw):
    return x


PLANTS = {"statistics": [("psum_scatter", own_rows)],
          "statistics_and_loss": [("psum_scatter", own_rows), ("pmean", own_value)]}
out = {}
with mock.patch.object(program, "train_config", fused):
    for name, plants in PLANTS.items():
        with mock.patch.multiple(jax.lax, **dict(plants)):
            res = harness.run_cell(root, "tiny.dp4", seed, 0.2, False, require_chip=False)[0]
        out[name] = {"correct": res["correct"], "checks": res["checks"],
                     "count": res["device"]["count"]}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def planted(tmp_path_factory):
    root = tmp_path_factory.mktemp("dp4x")
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
                       cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("plant", ["statistics", "statistics_and_loss"])
def test_leaving_out_the_exchange_fails_the_statistics_checks(planted, plant):
    run = planted[plant]
    assert run["count"] == 4
    assert run["correct"] is False
    over = {k for k, c in run["checks"].items() if c["value"] > c["limit"]}
    assert {"grad", "gsnr"} <= over, run["checks"]
