"""The sparse-expert family (``families/moe.py``) and its two readers: the
family's layout against the program's tree, its work counts by hand, the
readers on a hand-made trace and counter, and the program's counter logging
only while a profiler trace records."""
import importlib.util
import json
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from benchmarks.chip import program, spec, weights, work, xplane  # noqa: E402
from benchmarks.chip.peaks import peaks  # noqa: E402
from benchmarks.chip.run import RunInfo  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
CELL = "mellum2-12b-a2.5b.pack8k-k8"
TINY_WIDTHS = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                   moe_intermediate_size=32, router_experts=8, num_experts=2,
                   first_held_expert=2, num_experts_per_tok=2, vocab_size=256,
                   sliding_window=8, compute_dtype="float32")


@pytest.fixture(scope="module")
def cell():
    return spec.load_cell(ROOT, CELL)


def _tiny(conf):
    tiny = json.loads(json.dumps(conf))
    tiny.update(TINY_WIDTHS)
    return tiny


def _reader(name):
    s = importlib.util.spec_from_file_location(f"m_{name}", HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod.read


def test_the_configuration_keeps_the_published_widths(cell):
    """Every width is the source's; what differs is in ``reduced`` with its
    published value beside it."""
    conf = cell.config
    assert spec.family(conf).__file__ == str(HERE / "families" / "moe.py")
    published = {"hidden_size": 2304, "num_attention_heads": 32, "num_key_value_heads": 4,
                 "head_dim": 128, "moe_intermediate_size": 896, "num_experts_per_tok": 8,
                 "sliding_window": 1024, "rms_norm_eps": 1e-6, "router_experts": 64}
    assert {k: conf[k] for k in published} == published
    assert conf["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert conf["published"] == {"num_hidden_layers": 28, "num_experts": 64, "vocab_size": 98304}
    cfg = program.model_config(conf)
    assert cfg.block_pattern == ("swa", "swa", "swa", "attn") and cfg.sliding_window == 1024
    assert cfg.moe.dropless and (cfg.moe.n_experts, cfg.moe.held, cfg.moe.top_k) == (64, 8, 8)
    assert cfg.rope_yarn.factor == 16 and cfg.rope_yarn.original_max_positions == 8192


def test_the_family_tree_matches_init_params_and_round_trips(cell):
    conf = _tiny(cell.config)
    cfg = program.train_config(conf, cell.traffic)
    bp = weights.make_params(conf, 2**31 + 3)
    tree = program.to_program(bp, cfg, conf)  # raises unless it is init_params' tree
    assert tree["groups"][0]["pos3"]["moe"]["expert_wi"].shape == (2, 64, 32)
    assert tree["groups"][0]["pos0"]["moe"]["router"].shape == (64, 8)
    back = program.from_program(tree, conf)
    assert sorted(back) == sorted(bp) and all(back[k] is bp[k] for k in bp)


def test_pairs_per_kind_by_hand():
    """Window 4: a piece of 3 keeps all 6 causal pairs; one of 6 sees 1, 2,
    3, 4, 4, 4 keys (18 pairs, 21 causal)."""
    fam = spec.load_family(str(HERE / "families" / "moe.py"))
    conf = {"sliding_window": 4, "num_hidden_layers": 4, "layer_types":
            ["sliding_attention"] * 3 + ["full_attention"]}
    got = fam.pairs(conf, np.array([3, 6]))
    assert got == {"swa": 6 + 18, "attn": 6 + 21}
    assert fam.window_pairs(np.array([4, 5, 100]), 4) == 10 + 14 + 10 + 96 * 4


def test_window_pairs_match_a_brute_force_count_over_packed_rows(tmp_path):
    fam = spec.load_family(str(HERE / "families" / "moe.py"))
    tr = {"seq_len": 64, "rows": 4, "layout_seed": 3, "corpus_tokens": 3000,
          "docs": {"law": "lognormal", "median": 20, "sigma": 1.0, "min": 2, "max": 200},
          "markov": {"branching": 4, "probs": [0.55, 0.25, 0.15, 0.05]}}
    program.write_corpus(tr, 50, 7, tmp_path / "c")
    ds = program.dataset(str(tmp_path / "c"), tr)
    batch = ds.next_batch(8)
    pos, seg = batch["positions"], batch["segments"]
    pieces = program.piece_lengths(ds, 0, 8)
    assert pieces.max() > 7  # some pieces are longer than the window
    ok = ((pos[:, :, None] >= 0) & (pos[:, None, :] >= 0) & (seg[:, :, None] == seg[:, None, :])
          & (pos[:, None, :] <= pos[:, :, None]))
    assert fam.window_pairs(pieces, 7) == int((ok & (pos[:, None, :] > pos[:, :, None] - 7)).sum())


def test_model_flops_at_the_nominal_share(cell):
    """Per live token and layer: q, k, v, o (21.23 M), the router (0.15 M)
    and top_k * held / E = 1 expert's three matrices (6.19 M); the head
    once: 138.6 M weights, 18% of them experts."""
    conf = cell.config
    attn = 2 * 2304 * 32 * 128 + 2 * 2304 * 4 * 128
    expert = 3 * 2304 * 896
    want = 4 * (attn + 2304 * 64 + expert) + 2304 * 12288
    assert work.matmul_params(conf) == want == 138_608_640
    assert 0.17 < 4 * expert / want < 0.19
    pairs = {"swa": 1000, "attn": 3000}
    assert work.model_flops(conf, 10, pairs) == 6.0 * want * 10 + 12.0 * 32 * 128 * (3 * 1000 + 3000)
    assert work.attention_fwd(conf, 10, pairs, 2)[0] == 4.0 * 128 * 32 * 6000
    assert work.attention_bwd(conf, 10, pairs, 2)[0] == 8.0 * 128 * 32 * 6000
    # 70.93 M parameters a layer, 340.3 M in all
    assert work.param_count(conf) == 4 * (attn + 2304 * 64 + 8 * expert + 2 * 2304) \
        + 2 * 2304 * 12288 + 2304
    flops, nbytes = spec.family(conf).expert_work(conf, 1000, 8, 2)
    assert flops == 18.0 * 2304 * 896 * 1000
    assert nbytes == (5.0 * 2304 * 1000 + 3.0 * 3 * 8 * 2304 * 896 * 4 * 8) * 2


MOE = "jit(step)/jvp(model)/moe"
OPS = {  # name: (op_name, ns)
    "dot.1": (f"{MOE}/moe_route/dot_general", 100),
    "sort.2": (f"{MOE}/moe_dispatch/sort", 300),
    "gmm.3": (f"{MOE}/moe_experts/jit(gmm)", 2000),
    "tgmm.4": ("jit(step)/transpose(jvp(model))/moe/moe_experts/jit(tgmm)", 4000),
    "gather.5": (f"{MOE}/moe_combine/gather", 600),
    "fusion.6": ("jit(step)/jvp(model)/attn/dot_general", 5000),
}


def _run(conf, traffic, steps=2, op_scopes=None):
    t, ops = 0, []
    for _ in range(steps):
        for name, (_, ns) in OPS.items():
            ops.append(xplane.Op(name, t, ns, f"%{name} = f32[] custom-call()"))
            t += ns
    tr = xplane.Trace(ops={"/device:TPU:0": ops}, modules={"/device:TPU:0": []},
                      spans=[("window", 0, t)])
    return RunInfo(conf=conf, traffic=traffic, chips=1, peak=peaks("TPU v5 lite"), itemsize=2,
                   setup_s=1.0, trace=tr, traced_steps=steps,
                   op_scopes={n: v[0] for n, v in OPS.items()} if op_scopes is None else op_scopes)


def test_moe_ms_and_expert_roofline_read_a_hand_made_trace(cell, monkeypatch):
    from repro import obs

    run = _run(cell.config, cell.traffic)
    assert _reader("moe_ms")(run) == pytest.approx((100 + 300 + 2000 + 4000 + 600) * 1e-6)
    monkeypatch.setattr(obs, "traced_counts", lambda name: {obs.MOE_ROWS: [7, 1000, 3000]}[name])
    flops, nbytes = spec.family(cell.config).expert_work(cell.config, 4000, 2 * 8, 2)
    least = max(flops / 197e12, nbytes / 819e9)
    assert _reader("expert_roofline")(run) == pytest.approx(100.0 * least / (2 * 6000e-9))


def test_the_expert_readers_read_nothing_without_their_inputs(cell, monkeypatch):
    from benchmarks.chip import scopes
    from repro import obs

    moe_ms, roofline = _reader("moe_ms"), _reader("expert_roofline")
    monkeypatch.setattr(obs, "traced_counts", lambda name: [5, 5])
    dense = spec.load_cell(ROOT, "granite-3-2b.pack4k-k8")
    assert roofline(_run(dense.config, dense.traffic)) is None  # no expert_work
    run = _run(cell.config, cell.traffic)
    monkeypatch.setattr(obs, "traced_counts", lambda name: [5])  # fewer counts than steps
    assert roofline(run) is None and moe_ms(run) is not None
    no_moe = {n: "jit(step)/jvp(model)/attn/dot_general" for n in OPS}
    assert moe_ms(_run(cell.config, cell.traffic, op_scopes=no_moe)) is None
    assert moe_ms(RunInfo(conf=cell.config, traffic=cell.traffic, chips=1, peak=None,
                          itemsize=2, setup_s=1.0)) is None
    monkeypatch.setattr(scopes, "program_obs", lambda: None)  # a program without repro.obs
    assert moe_ms(run) is None and roofline(run) is None


def test_the_counter_logs_only_while_a_trace_records(tmp_path):
    import jax
    import jax.numpy as jnp
    from repro import obs

    @jax.jit
    def step(x):
        obs.count(obs.MOE_ROWS, jnp.sum(x))
        return x * 2

    before = len(obs.traced_counts(obs.MOE_ROWS))
    step(jnp.arange(4.0)).block_until_ready()
    jax.effects_barrier()
    assert len(obs.traced_counts(obs.MOE_ROWS)) == before
    jax.profiler.start_trace(str(tmp_path))
    try:
        for i in range(3):
            step(jnp.arange(4.0) + i).block_until_ready()
        jax.effects_barrier()
    finally:
        jax.profiler.stop_trace()
    assert obs.traced_counts(obs.MOE_ROWS)[before:] == [6, 10, 14]
    step(jnp.arange(4.0)).block_until_ready()
    jax.effects_barrier()
    assert len(obs.traced_counts(obs.MOE_ROWS)) == before + 3
