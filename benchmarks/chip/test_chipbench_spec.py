"""Discovery by name: a new configuration, traffic mix, cell and metric are
new files plus new entries in BENCHMARK.json, found without editing any file
that exists.  And the benchmark refuses to run without a TPU or without the
program."""
import hashlib
import json
import os
import pathlib
import shutil
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from benchmarks.chip import spec  # noqa: E402

BENCH = "benchmarks/chip"


def _checkout(tmp_path):
    """BENCHMARK.json and the benchmark's directory, nothing else."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / BENCH, tmp_path / BENCH,
                    ignore=shutil.ignore_patterns("__pycache__", ".*", "testdata"))
    return tmp_path


def _digests(root):
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()}


def test_every_cell_of_the_benchmark_loads():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = spec.load_cell(ROOT, w["name"])
        assert cell.chips == w["chips"] and cell.config["name"] == w["config"]
        assert {"loss", "grad", "change"} <= set(cell.limits)
        assert [m.name for m in cell.end_to_end] == ["tokens_per_s", "setup_s"]
        assert len(cell.per_layer) == len(bench["per_layer"])


def test_new_config_traffic_cell_and_metric_are_found_as_new_files(tmp_path):
    root = _checkout(tmp_path)
    before = _digests(root / BENCH)
    conf = json.loads((root / BENCH / "configs" / "bert-large.json").read_text())
    conf.update(name="bert-base", hidden_size=768, num_hidden_layers=12, num_attention_heads=12,
                num_key_value_heads=12)
    (root / BENCH / "configs" / "bert-base.json").write_text(json.dumps(conf))
    tr = json.loads((root / BENCH / "traffic" / "p1-k8.json").read_text())
    (root / BENCH / "traffic" / "p1-k4.json").write_text(json.dumps(dict(tr, k=4)))
    (root / BENCH / "limits" / "bert-base.p1-k4.json").write_text(
        (root / BENCH / "limits" / "bert-large.p1-k8.json").read_text())
    (root / BENCH / "metrics" / "window_steps.py").write_text(
        "def read(run):\n    return run.window_steps or None\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "bert-base", "source": "https://arxiv.org/abs/1810.04805",
                             "file": f"{BENCH}/configs/bert-base.json", "reduced": [],
                             "why": "test"})
    bench["workloads"].append({"name": "bert-base.p1-k4", "config": "bert-base",
                               "traffic": "p1-k4", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "window_steps", "unit": "steps", "better": "higher",
                               "source": "host_clock", "layer": "train step",
                               "moves": "tokens_per_s"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.load_cell(root, "bert-base.p1-k4")
    assert cell.config["hidden_size"] == 768 and cell.traffic["k"] == 4
    extra = [m for m in cell.per_layer if m.name == "window_steps"]
    assert len(extra) == 1 and extra[0].read(type("R", (), {"window_steps": 7})()) == 7
    # every cell reads every metric; a reader with nothing to read returns None
    old = [m for m in spec.load_cell(root, "bert-large.p1-k8").per_layer
           if m.name == "window_steps"]
    assert len(old) == 1 and old[0].read(type("R", (), {"window_steps": 0})()) is None
    after = _digests(root / BENCH)
    assert {k: v for k, v in after.items() if k in before} == before


def _run(cwd, env_extra, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run([sys.executable, f"{BENCH}/run.py", *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_a_run_without_a_tpu_exits_non_zero_and_prints_no_result():
    r = _run(ROOT, {}, "--workload", "bert-large.p1-k8", "--seed", "1", "--seconds", "1",
             "--trace", "0")
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_a_checkout_without_the_program_fails_before_any_result(tmp_path):
    root = _checkout(tmp_path)
    code = ("import sys; sys.path.insert(0, %r); import run; "
            "run.run_cell(%r, 'bert-large.p1-k8', 1, 1.0, False, require_chip=False)"
            % (str(root / BENCH), str(root)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    r = subprocess.run([sys.executable, "-c", code], cwd=root, env=env, capture_output=True,
                       text=True, timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "repro" in r.stderr
