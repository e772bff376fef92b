"""``correct`` at a size a test run can hold, judged by each cell's own
limits: a sound run of the timed path passes; the run driven with the timed
path broken underneath fails, once for each fault a training cell can have
on one chip (a step that returns its state unchanged; half of the batch
left out, the mean taken over the rest) and for a mean of squares replaced
by the square of the mean; the control, the reference in the program's
place with float8 matmuls, fails; and so does the reference in the
program's place with each of its planted faults.  The program computes in
float32 here, so that the sound run's gaps are rounding alone."""
import contextlib
import json
import pathlib
import shutil
import sys
from unittest import mock

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from benchmarks.chip import check, reference  # noqa: E402
from benchmarks.chip import run as harness  # noqa: E402

BENCH = ROOT / "benchmarks" / "chip"
CELLS = ("bert-large.p1-k8", "granite-3-2b.pack4k-k8", "bert-large.p1-dp4")
SEED = 2**31 + 29
CONF = {"name": "tiny", "source": "test", "hidden_size": 64, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "intermediate_size": 128, "vocab_size": 256, "hidden_act": "silu", "mlp": "gated",
        "norm": "rmsnorm", "norm_eps": 1e-6, "causal": True, "rope_theta": 10000.0,
        "tie_word_embeddings": True, "param_dtype": "float32", "compute_dtype": "float32",
        "reduced": []}


def unchanged_state(step):
    def broken(state, batch):
        return state, step(state, batch)[1]
    return broken


def half_batch(step):
    def broken(state, batch):
        return step(state, reference.half_batch(batch))
    return broken


def sq_mean2():
    """The statistics the program computes with the square of the mean in
    place of the mean of squares: no variance reaches the GSNR."""
    import jax
    import jax.numpy as jnp
    from repro.train import trainer

    real = trainer.grad_stats

    def broken(*args, **kwargs):
        loss, aux, stats = real(*args, **kwargs)
        return loss, aux, stats._replace(sq_mean=jax.tree_util.tree_map(jnp.square, stats.mean))

    return mock.patch.object(trainer, "grad_stats", broken)


# fault -> (wrap of the timed step, patch of the program), either may be None
FAULTS = {"sound": (None, None), "unchanged_state": (unchanged_state, None),
          "half_batch": (half_batch, None), "sq_mean2": (None, sq_mean2)}


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("tiny")
    bench = root / "bench"
    for d in ("configs", "traffic", "limits"):
        (bench / d).mkdir(parents=True)
    for d in ("metrics", "families"):
        shutil.copytree(BENCH / d, bench / d, ignore=shutil.ignore_patterns("__pycache__"))
    (bench / "configs" / "tiny.json").write_text(json.dumps(CONF))
    tr = json.loads((BENCH / "traffic" / "pack4k-k8.json").read_text())
    # k = 8 as in the cells: from two microbatches the GSNR is a ratio of
    # near-cancelling differences, and rounding alone moves it
    tr.update(seq_len=32, rows=8, k=8, corpus_tokens=20000, checked_steps=3, trace_steps=2)
    tr["docs"].update(median=16, min=2, max=128)
    (bench / "traffic" / "tiny.json").write_text(json.dumps(tr))
    shutil.copy(BENCH / "limits" / f"{CELLS[0]}.json", bench / "limits" / "tiny.t.json")
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    b.update(paths=["bench"],
             configs=[{"name": "tiny", "source": "test", "file": "bench/configs/tiny.json",
                       "reduced": [], "why": "test"}],
             workloads=[{"name": "tiny.t", "config": "tiny", "traffic": "tiny", "chips": 1,
                         "why": "test"}])
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    return root


@pytest.fixture(scope="module")
def runs(tiny_root):
    """One whole run (set-up, window, reference, comparison) per fault."""
    out = {}
    for name, (wrap, patch) in FAULTS.items():
        with patch() if patch else contextlib.nullcontext():
            out[name] = harness.run_cell(tiny_root, "tiny.t", SEED, 0.2, False,
                                         require_chip=False, wrap_step=wrap)[0]
    return out


def _limits(cell):
    return json.loads((BENCH / "limits" / f"{cell}.json").read_text())


def test_the_run_reports_correct_under_the_first_cells_limits(runs):
    assert runs["sound"]["correct"] is True
    assert runs["unchanged_state"]["correct"] is False
    assert runs["half_batch"]["correct"] is False
    assert runs["sq_mean2"]["correct"] is False
    assert list(runs["sound"])[-1] == "checks"


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", list(FAULTS))
def test_each_fault_fails_and_the_sound_run_passes(runs, fault, cell):
    values = {k: v["value"] for k, v in runs[fault]["checks"].items()}
    ok, _, _ = check.judge(values, _limits(cell))
    assert ok is (fault == "sound"), values


def test_an_unchanged_state_reads_one_on_change(runs):
    assert runs["unchanged_state"]["checks"]["change"]["value"] == pytest.approx(1.0)


@pytest.fixture(scope="module")
def in_place(tiny_root):
    """The reference in the program's place: the control, and each fault
    planted in it, against the sound reference."""
    from benchmarks.chip import program, spec

    cell = spec.load_cell(tiny_root, "tiny.t")
    conf = dict(cell.config, compute_dtype="bfloat16")
    cache = tiny_root / "control-corpus"
    program.write_corpus(cell.traffic, conf["vocab_size"], SEED, cache)
    host = program.host_batches(program.dataset(str(cache), cell.traffic), 3)
    ref = reference.readings(conf, cell.traffic, SEED, host)
    out = {"control": check.numbers(
        reference.readings(conf, cell.traffic, SEED, host, quant=True), ref)}
    for fault in reference.FAULTS:
        out[fault] = check.numbers(
            reference.readings(conf, cell.traffic, SEED, host, fault=fault), ref)
    return out


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_fails(in_place, cell):
    ok, _, _ = check.judge(in_place["control"], _limits(cell))
    assert ok is False, in_place["control"]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", reference.FAULTS)
def test_each_fault_planted_in_the_reference_fails(in_place, fault, cell):
    ok, _, _ = check.judge(in_place[fault], _limits(cell))
    assert ok is False, in_place[fault]
