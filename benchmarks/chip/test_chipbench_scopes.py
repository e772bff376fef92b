"""The train step's phases from the program's named scopes: the compiled
step's text parsed into op_names, the phase rules, the scopes of a tiny
fused step compiled on the CPU, and the readers of the six per-layer
metrics that read the program's scopes and spans, on hand-made traces."""
import dataclasses
import gzip
import importlib.util
import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from benchmarks.chip import program, scopes, xplane  # noqa: E402
from benchmarks.chip.run import RunInfo  # noqa: E402
from repro import obs  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
PHASE_METRICS = {"forward_ms": "forward", "recompute_ms": "recompute",
                 "backward_ms": "backward", "grad_pack_ms": "grad_pack",
                 "optimizer_ms": "optimizer"}
NEW_METRICS = (*PHASE_METRICS, "data_produce_ms")
TINY = {"name": "tiny", "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128, "vocab_size": 256,
        "hidden_act": "silu", "mlp": "gated", "norm": "rmsnorm", "norm_eps": 1e-6,
        "causal": True, "rope_theta": 10000.0, "tie_word_embeddings": True,
        "param_dtype": "float32", "compute_dtype": "bfloat16"}

HLO = r"""HloModule jit_step, entry_computation_layout={(f32[4]{0})->f32[16]{0}}

%fused_pad (param_0.2: f32[4]) -> f32[16] {
  %param_0.2 = f32[4]{0} parameter(0)
  %constant.5 = f32[] constant(0), metadata={op_name="jit(step)/while/body/closed_call"}
  %pad.6 = f32[8]{0} pad(%param_0.2, %constant.5), padding=0_4, metadata={op_name="jit(step)/while/body/closed_call/stats_pack/jit(_pad)/pad" source_file="x.py" source_line=3}
  %custom-call.7 = f32[16]{0:T(1024)} custom-call(), custom_call_target="AllocateBuffer"
  %constant.9 = s32[] constant(0)
  ROOT %dynamic-update-slice.8 = f32[16]{0} dynamic-update-slice(%custom-call.7, %pad.6, %constant.9)
}

%body (param.10: (f32[16], f32[4])) -> (f32[16], f32[4]) {
  %param.10 = (f32[16]{0}, f32[4]{0}) parameter(0)
  %get-tuple-element.11 = f32[4]{0} get-tuple-element(%param.10), index=1
  %pad_dynamic-update-slice_fusion.12 = f32[16]{0} fusion(%get-tuple-element.11), kind=kLoop, calls=%fused_pad
  %constant.17 = s32[] constant(8)
  %dynamic-update-slice.13 = f32[16]{0} dynamic-update-slice(%pad_dynamic-update-slice_fusion.12, %get-tuple-element.11, %constant.17)
  %copy.14 = f32[4]{0} copy(%get-tuple-element.11)
  ROOT %tuple.15 = (f32[16]{0}, f32[4]{0}) tuple(%dynamic-update-slice.13, %copy.14)
}

ENTRY %main.20 (state.1: f32[4]) -> (f32[16], f32[4]) {
  %state.1 = f32[4]{0} parameter(0), metadata={op_name="state.params[\'embed\']"}
  %broadcast.18 = f32[16]{0} broadcast(%constant.19), dimensions={}
  %tuple.21 = (f32[16]{0}, f32[4]{0}) tuple(%broadcast.18, %state.1)
  ROOT %while.16 = (f32[16]{0}, f32[4]{0}) while(%tuple.21), condition=%cond, body=%body, metadata={op_name="jit(step)/while"}
}
"""
PACK = "jit(step)/while/body/closed_call/stats_pack/jit(_pad)/pad"

BODY = "jit(step)/while/body/closed_call"
LAYER = f"{BODY}/transpose(jvp(model))/while/body/closed_call/checkpoint"
# instruction -> (op_name, phase, ns per step)
HAND = {
    "fusion.1": (f"{BODY}/jvp(model)/while/body/closed_call/dot_general", "forward", 7000),
    "flash_attention.2": (f"{BODY}/jvp(model)/while/body/closed_call/jit(flash_attention)",
                          "forward", 500),
    "fusion.3": (f"{LAYER}/rematted_computation/dot_general", "recompute", 6000),
    "fusion.4": (f"{LAYER}/dot_general", "backward", 13000),
    "fusion.5": (f"{BODY}/transpose(jvp(model))/reduce_sum", "backward", 1000),
    "concatenate.6": (f"{BODY}/stats_pack/concatenate", "grad_pack", 3000),
    "flat_moments_accum.7": (f"{BODY}/stats_accum/jit(flat_moments_accum)", "stats", 4000),
    "flat_moments_finalize.8": ("jit(step)/stats_finalize/jit(flat_moments_finalize)",
                                "stats", 500),
    "flat_vr_lamb.9": ("jit(step)/optimizer/jit(flat_vr_lamb)", "optimizer", 4500),
    "copy.10": ("", "other", 200),
    "add.11": (f"{BODY}/add", "other", 100),
}


def _reader(name):
    spec = importlib.util.spec_from_file_location(f"m_{name}", HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _hand_run(steps=2, trace=True, op_scopes=None):
    t, ops = 0, []
    for _ in range(steps):
        for name, (_, _, ns) in HAND.items():
            ops.append(xplane.Op(name, t, ns, f"%{name} = f32[] custom-call()"))
            t += ns
    tr = xplane.Trace(ops={"/device:TPU:0": ops}, modules={"/device:TPU:0": []},
                      spans=[("window", 0, t)]) if trace else None
    if op_scopes is None:
        op_scopes = {n: v[0] for n, v in HAND.items()}
    return RunInfo(conf=TINY, traffic={"k": 2, "checked_steps": 3}, chips=1, peak=None,
                   itemsize=2, setup_s=1.0, trace=tr, traced_steps=steps if trace else 0,
                   op_scopes=op_scopes if trace else None)


def test_op_scopes_reads_every_instruction_of_the_text():
    got = scopes.op_scopes(HLO)
    assert got["pad.6"] == PACK and got["while.16"] == "jit(step)/while"
    assert got["state.1"] == r"state.params[\'embed\']"
    assert set(got) == {"param_0.2", "constant.5", "pad.6", "custom-call.7", "constant.9",
                        "dynamic-update-slice.8", "param.10", "get-tuple-element.11",
                        "pad_dynamic-update-slice_fusion.12", "constant.17",
                        "dynamic-update-slice.13", "copy.14", "tuple.15", "state.1",
                        "broadcast.18", "tuple.21", "while.16"}


def test_an_instruction_without_an_op_name_takes_what_it_was_made_from():
    got = scopes.op_scopes(HLO)
    # a fusion: its fused instructions, from the root up
    assert got["pad_dynamic-update-slice_fusion.12"] == PACK
    # else its first operand: the buffer a dynamic-update-slice writes into
    assert got["dynamic-update-slice.13"] == PACK
    assert got["dynamic-update-slice.8"] == PACK
    # else the loop that runs it
    assert got["copy.14"] == got["get-tuple-element.11"] == "jit(step)/while"
    # and "" where nothing has one
    assert got["broadcast.18"] == ""


@pytest.mark.parametrize("name", list(HAND))
def test_phase_rules(name):
    op_name, want, _ = HAND[name]
    assert scopes.phase(op_name) == want


def test_phase_matches_whole_path_components_and_the_first_rule_wins():
    assert scopes.phase("jit(step)/models/dot_general") == "other"
    assert scopes.phase("jit(step)/optimizer/jit(flat_vr_lamb)/model/mul") == "optimizer"
    assert scopes.phase(f"{BODY}/jvp(model)/while/body/rematted_computation/mul") == "recompute"


@pytest.fixture(scope="module")
def tiny_step_scopes():
    """op_scopes of the benchmark's step at a tiny size on the fused plan
    (Pallas in interpret mode): the CPU's auto plan is the reference."""
    import jax

    from benchmarks.chip import weights
    from repro.backend import Backend

    tr = json.loads((HERE / "traffic" / "p1-k8.json").read_text())
    tr.update(seq_len=32, rows=4, k=2)
    cfg = program.train_config(TINY, tr)
    cfg = cfg.replace(parallel=dataclasses.replace(cfg.parallel, backend=Backend.all_fused()))
    state = jax.eval_shape(lambda: program.init_state(cfg, weights.make_params(TINY, 0), TINY))
    compiled = program.make_step(cfg).lower(state, program.batch_shapes(tr)).compile()
    return scopes.op_scopes(compiled.as_text())


def test_a_compiled_fused_step_holds_every_phase(tiny_step_scopes):
    found = {}
    for name, op_name in tiny_step_scopes.items():
        found.setdefault(scopes.phase(op_name), []).append(name)
    assert set(found) == set(scopes.PHASES), sorted(found)


def test_the_phase_readers_on_a_hand_made_trace():
    run = _hand_run()
    for metric, ph in PHASE_METRICS.items():
        want = sum(ns for _, p, ns in HAND.values() if p == ph) * 1e-6
        assert _reader(metric)(run) == pytest.approx(want), metric
    ns = scopes.phase_ns(run.trace, {n: v[0] for n, v in HAND.items()})
    assert ns["other"] == 2 * 300 and sum(ns.values()) == xplane.op_ns(run.trace, lambda o: True)


def test_the_phase_readers_read_nothing_without_their_inputs(monkeypatch):
    for metric in PHASE_METRICS:
        assert _reader(metric)(_hand_run(trace=False)) is None
    # a run that kept no text of its step
    no_text = dataclasses.replace(_hand_run(), op_scopes=None)
    assert all(_reader(m)(no_text) is None for m in PHASE_METRICS)
    # a text that is not the traced program's: an op of the trace is missing
    other = {n: v[0] for n, v in HAND.items() if n != "copy.10"}
    assert all(_reader(m)(_hand_run(op_scopes=other)) is None for m in PHASE_METRICS)
    # a program that predates repro.obs
    monkeypatch.setattr(scopes, "program_obs", lambda: None)
    assert all(_reader(m)(_hand_run()) is None for m in NEW_METRICS)


def test_data_produce_reads_the_traced_spans(monkeypatch):
    spans = [2_000_000, 4_000_000]
    monkeypatch.setattr(obs, "traced_durations", lambda name: list(spans))
    read = _reader("data_produce_ms")
    assert read(_hand_run()) == pytest.approx(3.0)
    assert read(_hand_run(trace=False)) is None
    spans.clear()
    assert read(_hand_run()) is None


# the accepted readers on the recorded chip trace of the accepted fixture,
# with the hand-made run that test_chipbench_xplane reads it with: the
# program's scopes renamed no kernel they select by
ACCEPTED = {"data_wait_ms": 0.04008, "mfu": 1.3167092809972978, "stats_ms": 0.0200685,
            "stats_roofline": 127.71885109130042, "update_ms": 0.108903,
            "update_roofline": 58.83965003548485, "attn_fwd_roofline": 2.445138459427619,
            "attn_bwd_roofline": 6.892215078167579, "device_idle_share": 78.18084108685774}


def test_the_accepted_readers_read_as_before_on_the_accepted_fixture():
    from benchmarks.chip.peaks import peaks

    conf = {"hidden_size": 256, "num_hidden_layers": 2, "num_attention_heads": 4,
            "num_key_value_heads": 2, "head_dim": 64, "intermediate_size": 512,
            "vocab_size": 512, "mlp": "gated", "norm": "rmsnorm", "tie_word_embeddings": True}
    info = RunInfo(conf=conf, traffic={"k": 2}, chips=1, peak=peaks("TPU v5 lite"), itemsize=2,
                   setup_s=1.0, trace=xplane.load(str(HERE / "testdata" /
                                                      "tiny_two_steps.xplane.pb")),
                   traced_steps=2, traced_tokens=2000, traced_pairs=60000)
    assert {n: _reader(n)(info) for n in ACCEPTED} == ACCEPTED


SCOPED = HERE / "testdata" / "tiny_scoped"


@pytest.fixture(scope="module")
def scoped():
    """A chip trace of two steps of a 2-layer, d_model 256 model recorded
    through the harness (``tiny_scoped.xplane.pb``), the text of the step it
    ran (``tiny_scoped.hlo.txt.gz``) and the program spans in the trace."""
    import jax

    text = gzip.decompress((HERE / "testdata" / "tiny_scoped.hlo.txt.gz").read_bytes()).decode()
    data = jax.profiler.ProfileData.from_file(f"{SCOPED}.xplane.pb")
    spans = [int(e.duration_ns) for p in data.planes if p.name.startswith("/host:")
             for line in p.lines for e in line.events if e.name == obs.DATA_PRODUCE]
    return scopes.op_scopes(text), xplane.load(f"{SCOPED}.xplane.pb"), spans


def test_the_new_readers_read_the_scoped_chip_fixture(scoped, monkeypatch):
    op_scopes, trace, spans = scoped
    assert spans and all(d > 0 for d in spans)
    monkeypatch.setattr(obs, "traced_durations", lambda name: spans)
    run = RunInfo(conf=TINY, traffic={"k": 2}, chips=1, peak=None, itemsize=2, setup_s=1.0,
                  trace=trace, traced_steps=2, op_scopes=op_scopes)
    values = {n: _reader(n)(run) for n in NEW_METRICS}
    assert all(v is not None and v > 0 for v in values.values()), values


def test_the_scoped_chip_fixture_owns_its_ops_by_phase(scoped):
    op_scopes, trace, _ = scoped
    ns = scopes.phase_ns(trace, op_scopes)
    assert ns is not None and all(ns[p] > 0 for p in scopes.PHASES)
    # every attention op reading the softmax statistics is in the backward,
    # and the rematerialized layer reruns each forward attention call once
    attn = [o for o in trace.ops["/device:TPU:0"] if xplane.named("flash_attention")(o)]
    phases = [(scopes.phase(op_scopes[o.name]), xplane.reads_row_stats(o)) for o in attn]
    assert {p for p, bwd in phases if bwd} == {"backward"}
    fwd = [p for p, bwd in phases if not bwd]
    assert set(fwd) == {"forward", "recompute"}
    assert fwd.count("forward") == fwd.count("recompute")
    # the kernels the accepted readers select by name sit in their phases
    for kernel, want in (("flat_moments_accum", "stats"), ("flat_moments_finalize", "stats"),
                         ("flat_vr_lamb", "optimizer")):
        got = {scopes.phase(op_scopes[o.name]) for o in trace.ops["/device:TPU:0"]
               if xplane.named(kernel)(o)}
        assert got == {want}, kernel
