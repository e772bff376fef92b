"""The trace reduction on a small recorded chip trace (``testdata/
tiny_two_steps.xplane.pb``: two steps of a 2-layer, d_model 256 model
through the harness's own window on one TPU v5e) and on hand-made
intervals: busy union, idle share, op-to-layer attribution and the readers
that turn them into metrics."""
import importlib.util
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from benchmarks.chip import xplane  # noqa: E402
from benchmarks.chip.peaks import peaks  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
FIXTURE = HERE / "testdata" / "tiny_two_steps.xplane.pb"
TINY = {"hidden_size": 256, "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 64, "intermediate_size": 512, "vocab_size": 512,
        "mlp": "gated", "norm": "rmsnorm", "tie_word_embeddings": True}


@pytest.fixture(scope="module")
def recorded():
    return xplane.load(str(FIXTURE))


def _reader(name):
    spec = importlib.util.spec_from_file_location(f"m_{name}", HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_recorded_trace_holds_the_window_and_both_steps(recorded):
    names = [s[0] for s in recorded.spans]
    assert names.count("window") == 1
    assert names.count("data_wait") == 2 and names.count("dispatch") == 2
    assert len(recorded.modules["/device:TPU:0"]) == 2
    assert list(recorded.ops) == ["/device:TPU:0"]
    assert not any(xplane.CONTAINER.match(o.name) for o in recorded.ops["/device:TPU:0"])


def test_recorded_busy_union_and_idle_share(recorded):
    busy = xplane.mean_busy_s(recorded)
    ops = recorded.ops["/device:TPU:0"]
    assert 0 < busy <= sum(o.dur for o in ops) * 1e-9
    assert busy < recorded.window_s
    gaps = xplane.idle_gaps(recorded)
    assert gaps and all(label in xplane.SPANS for label, _ in gaps)
    # the two steps are host-bound: the longest gaps fall in dispatch
    assert gaps[0][0] == "dispatch"


def test_recorded_ops_attribute_to_layers(recorded):
    stats = xplane.op_ns(recorded, xplane.named("flat_moments_accum", "flat_moments_finalize"))
    attn = [o for o in recorded.ops["/device:TPU:0"] if xplane.named("flash_attention")(o)]
    bwd = [o for o in attn if xplane.reads_row_stats(o)]
    # per step and layer: two forwards (the step and its rematerialization), one backward
    assert len(attn) == 2 * 2 * 2 * 3 and len(bwd) == 2 * 2 * 2
    assert stats > 0
    update = xplane.busy_after(recorded, xplane.named("flat_vr_lamb"))
    assert 0 < update < xplane.busy_ns(recorded, "/device:TPU:0")


def test_readers_on_the_recorded_trace(recorded):
    from benchmarks.chip.run import RunInfo

    info = RunInfo(conf=TINY, traffic={"k": 2}, chips=1, peak=peaks("TPU v5 lite"), itemsize=2,
                   setup_s=1.0, trace=recorded, traced_steps=2, traced_tokens=2000,
                   traced_pairs=60000)
    values = {n: _reader(n)(info) for n in (
        "data_wait_ms", "mfu", "stats_ms", "stats_roofline", "update_ms", "update_roofline",
        "attn_fwd_roofline", "attn_bwd_roofline", "device_idle_share")}
    # at this size the arrays fit in the chip's VMEM, so the HBM-byte
    # rooflines of the full-size cells mean nothing here: only that each
    # reader finds its ops
    assert all(v is not None and v > 0 for v in values.values()), sorted(values.items())
    assert values["device_idle_share"] < 100 and values["mfu"] < 100
    assert _reader("tokens_per_s")(info) is None and _reader("setup_s")(info) is None


def _hand_trace(ops, spans):
    return xplane.Trace(
        ops={"/device:TPU:0": [xplane.Op(n, s, d, f"%{n} = f32[] custom-call()") for n, s, d in ops]},
        modules={"/device:TPU:0": [("jit_step", 0, 100)]}, spans=spans)


def test_union_merges_overlaps_and_clips():
    assert xplane.union([(0, 10), (5, 20), (30, 40), (35, 36)], 0, 100) == [(0, 20), (30, 40)]
    assert xplane.union([(0, 10), (90, 120)], 5, 100) == [(5, 10), (90, 100)]


def test_hand_trace_busy_gaps_and_attribution():
    t = _hand_trace([("flat_moments_accum.1", 0, 10), ("fusion.2", 5, 15),
                     ("flat_vr_lamb.3", 30, 10), ("fusion.4", 45, 5)],
                    [("window", 0, 100), ("dispatch", 20, 10), ("sync", 50, 50)])
    assert xplane.busy_ns(t, "/device:TPU:0") == 20 + 10 + 5
    gaps = xplane.idle_gaps(t)
    assert [g[0] for g in gaps] == ["sync", "dispatch", "none"]
    assert [g[1] for g in gaps] == pytest.approx([50e-9, 10e-9, 5e-9])
    assert xplane.op_ns(t, xplane.named("flat_moments_accum")) == 10
    # from the update kernel's start to the module's end: 30..40 and 45..50
    assert xplane.busy_after(t, xplane.named("flat_vr_lamb")) == 15
    top = xplane.top_ops(t, 2)
    assert [n for n, _ in top] == ["fusion.2", "flat_moments_accum.1"]
    assert [v for _, v in top] == pytest.approx([15e-9, 10e-9])


def _hlo_op(name, start, dur, opcode):
    return xplane.Op(name, start, dur, f"%{name} = (f32[8]{{0:T(128)}}, f32[8]) {opcode}(%x)")


# one plane: a collective that compute covers, one that nothing covers, an
# asynchronous pair whose start launches it and whose done is the wait, and
# one that compute covers in part
COLLECTIVE_OPS = [
    _hlo_op("fusion.1", 0, 100, "fusion"),
    _hlo_op("all-reduce.2", 20, 40, "all-reduce"),  # covered: 0
    _hlo_op("all-gather.3", 120, 30, "all-gather"),  # bare: 30
    _hlo_op("all-reduce-start.4", 160, 2, "all-reduce-start"),
    _hlo_op("fusion.5", 162, 28, "fusion"),
    _hlo_op("all-reduce-done.6", 190, 10, "all-reduce-done"),  # the wait: 10
    _hlo_op("reduce-scatter.7", 210, 30, "reduce-scatter"),
    _hlo_op("fusion.8", 230, 30, "fusion"),  # covers the last 10 of it: 20
]


def test_collectives_are_told_by_their_opcode():
    picked = [o.name for o in COLLECTIVE_OPS if xplane.is_collective(o)]
    assert picked == ["all-reduce.2", "all-gather.3", "all-reduce-done.6", "reduce-scatter.7"]
    assert xplane.opcode(COLLECTIVE_OPS[0]) == "fusion"
    assert xplane.opcode(xplane.Op("x", 0, 1, "no instruction text")) == ""
    for code in ("all-to-all", "collective-permute", "collective-permute-done"):
        assert xplane.is_collective(_hlo_op("c.1", 0, 1, code)), code
    assert not xplane.is_collective(_hlo_op("c.1", 0, 1, "all-gather-start"))


def test_exposed_time_counts_only_what_no_other_op_covers():
    tr = xplane.Trace(ops={"/device:TPU:0": COLLECTIVE_OPS}, modules={},
                      spans=[("window", 0, 300)])
    assert xplane.exposed_ns(tr, "/device:TPU:0", xplane.is_collective) == 0 + 30 + 10 + 20
    assert xplane.overlap_ns([(0, 10), (20, 30)], [(5, 25)]) == 5 + 5


def test_collective_exposed_ms_averages_the_chips_per_step():
    covered = [_hlo_op("fusion.1", 0, 100, "fusion"), _hlo_op("all-reduce.2", 20, 40, "all-reduce")]
    tr = xplane.Trace(ops={"/device:TPU:0": COLLECTIVE_OPS, "/device:TPU:1": covered},
                      modules={}, spans=[("window", 0, 300)])
    read = _reader("collective_exposed_ms")
    run = type("R", (), {"trace": tr, "traced_steps": 2})()
    assert read(run) == pytest.approx((60 + 0) / 2 * 1e-6 / 2)
    # one chip, no collectives: nothing to read
    solo = xplane.Trace(ops={"/device:TPU:0": COLLECTIVE_OPS[:1]}, modules={},
                        spans=[("window", 0, 300)])
    assert read(type("R", (), {"trace": solo, "traced_steps": 2})()) is None
    assert read(type("R", (), {"trace": None, "traced_steps": 0})()) is None


def test_the_recorded_one_chip_trace_has_no_collectives(recorded):
    assert not any(xplane.is_collective(o) for o in recorded.ops["/device:TPU:0"])
    assert all(xplane.opcode(o) for o in recorded.ops["/device:TPU:0"])
