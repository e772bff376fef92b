"""Model families as files: the dense family counts what the harness counted
before it became a family, and a new family is one more file under
``families/`` plus its configuration, traffic, limits and cell, run end to
end without editing any file that exists."""
import hashlib
import json
import pathlib
import shutil
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from benchmarks.chip import run as harness  # noqa: E402
from benchmarks.chip import program, spec, weights, work  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
BENCH = "benchmarks/chip"
SEED = 2**31 + 101

# the harness's counts of the two accepted configurations, read before the
# dense model moved into families/dense.py
PINNED = {
    "bert-large": {
        "param_count": 333344768, "matmul_params": 333244416,
        "model_flops": 16416038191104.0,
        "attention_fwd": (12136218624.0, 1610612736.0),
        "attention_bwd": (24272437248.0, 3221225472.0),
        "leaf_shapes": {
            "embed": (30522, 1024), "layers.ln1_scale": (24, 1024),
            "layers.ln1_bias": (24, 1024), "layers.ln2_scale": (24, 1024),
            "layers.ln2_bias": (24, 1024), "layers.wq": (24, 1024, 1024),
            "layers.wk": (24, 1024, 1024), "layers.wv": (24, 1024, 1024),
            "layers.wo": (24, 1024, 1024), "layers.wi": (24, 1024, 4096),
            "layers.wd": (24, 4096, 1024), "final.scale": (1024,), "final.bias": (1024,)},
    },
    "granite-3-2b": {
        "param_count": 343957504, "matmul_params": 343939072,
        "model_flops": 16917429485568.0,
        "attention_fwd": (4045406208.0, 335544320.0),
        "attention_bwd": (8090812416.0, 671088640.0),
        "leaf_shapes": {
            "embed": (49155, 2048), "layers.ln1_scale": (4, 2048),
            "layers.ln2_scale": (4, 2048), "layers.wq": (4, 2048, 2048),
            "layers.wk": (4, 2048, 512), "layers.wv": (4, 2048, 512),
            "layers.wo": (4, 2048, 2048), "layers.wi": (4, 2048, 8192),
            "layers.wd": (4, 8192, 2048), "layers.wg": (4, 2048, 8192),
            "final.scale": (2048,)},
    },
}


@pytest.mark.parametrize("name", list(PINNED))
def test_the_dense_family_counts_as_before(name):
    conf = spec.load_cell(ROOT, {"bert-large": "bert-large.p1-k8",
                                 "granite-3-2b": "granite-3-2b.pack4k-k8"}[name]).config
    pin = PINNED[name]
    assert spec.family(conf).__file__ == str(HERE / "families" / "dense.py")
    assert weights.leaf_shapes(conf) == pin["leaf_shapes"]
    assert work.param_count(conf) == pin["param_count"]
    assert work.matmul_params(conf) == pin["matmul_params"]
    assert work.model_flops(conf, 8192, 123456) == pin["model_flops"]
    assert work.attention_fwd(conf, 8192, 123456, 2) == pin["attention_fwd"]
    assert work.attention_bwd(conf, 8192, 123456, 2) == pin["attention_bwd"]


def test_a_configuration_without_a_family_is_dense_and_an_unknown_one_is_refused():
    assert spec.family({"name": "x"}) is spec.family({"family": "dense"})
    with pytest.raises(FileNotFoundError, match="no module"):
        spec.family({"family": "no-such-family"})


def _digests(root):
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()}


def test_a_family_added_as_files_only_runs_correct(tmp_path):
    """A toy family (``testdata/toy_moe2.py``: two experts under a softmax
    router) with its configuration, traffic, limits and cell, all new files
    in a copy of the benchmark, and its entries in BENCHMARK.json: a whole
    run on the CPU is correct, and no file of the benchmark that was there
    changed."""
    root = tmp_path
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(ROOT / BENCH, root / BENCH,
                    ignore=shutil.ignore_patterns("__pycache__", ".*", "testdata"))
    bench = root / BENCH
    before = _digests(bench)

    shutil.copy(HERE / "testdata" / "toy_moe2.py", bench / "families" / "moe2.py")
    conf = {"name": "tiny-moe2", "family": "moe2", "source": "test", "hidden_size": 64,
            "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 2,
            "head_dim": 16, "intermediate_size": 128, "vocab_size": 256, "hidden_act": "silu",
            "mlp": "gated", "norm": "rmsnorm", "norm_eps": 1e-6, "causal": True,
            "rope_theta": 10000.0, "tie_word_embeddings": True, "param_dtype": "float32",
            "compute_dtype": "float32", "reduced": []}
    (bench / "configs" / "tiny-moe2.json").write_text(json.dumps(conf))
    tr = json.loads((bench / "traffic" / "pack4k-k8.json").read_text())
    tr.update(seq_len=32, rows=8, k=8, corpus_tokens=20000, checked_steps=3, trace_steps=2)
    tr["docs"].update(median=16, min=2, max=128)
    (bench / "traffic" / "tiny.json").write_text(json.dumps(tr))
    shutil.copy(bench / "limits" / "bert-large.p1-k8.json",
                bench / "limits" / "tiny-moe2.tiny.json")
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "tiny-moe2", "source": "test",
                         "file": f"{BENCH}/configs/tiny-moe2.json", "reduced": [],
                         "why": "test"})
    b["workloads"].append({"name": "tiny-moe2.tiny", "config": "tiny-moe2",
                           "traffic": "tiny", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(b))

    cell = spec.load_cell(root, "tiny-moe2.tiny")
    fam = spec.family(cell.config)
    assert pathlib.Path(fam.__file__) == bench / "families" / "moe2.py"
    cfg = program.train_config(cell.config, cell.traffic)
    assert cfg.model.moe is not None and cfg.model.moe.n_experts == 2
    # the dense stack's weights, a second expert (3 * 64 * 128) and the
    # router (64 * 2) in each of the two layers
    assert work.matmul_params(cell.config) == work.matmul_params(
        dict(cell.config, family="dense", family_file="")) + 2 * (3 * 64 * 128 + 64 * 2)

    result, _ = harness.run_cell(root, "tiny-moe2.tiny", SEED, 0.2, False, require_chip=False)
    assert result["correct"] is True, result["checks"]
    after = _digests(bench)
    assert {k: v for k, v in after.items() if k in before} == before
