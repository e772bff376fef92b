"""Compile the main-path Pallas kernels for a described TPU v5e at real
widths — no chip needed.

Interpret mode runs the kernel bodies on the CPU but knows nothing of the
TPU compiler's block-tiling rule, its VMEM limit or what Mosaic can lower,
so every interpret-mode test can pass while the chip refuses the kernel.
Here each kernel is lowered and compiled for one chip of a described
``v5e:2x2`` topology, at the widths the training path runs (BERT-large
attention and flat state, granite-3-2b GQA and granite-20b MQA attention
at seq 4096, and the dropless expert layer's grouped matmuls at
Mellum2-12B-A2.5B's widths), and the compiled program must hold the Mosaic
custom call.

The topology is described inside a module fixture — never at import — so
pytest-xdist workers collect identical tests and only the worker that runs
this file loads the TPU compiler library.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.layout import LANE, ParamLayout
from repro.models import init_params

BF16, I32 = jnp.bfloat16, jnp.int32


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs in /tmp

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    """The four chips of the described host as a data-parallel mesh."""
    from jax.sharding import Mesh

    return Mesh(np.array(topo.devices[:4]), ("data",))


@pytest.fixture(scope="module")
def bert_layout():
    """The flat ParamLayout of full-width BERT-large (24 layers)."""
    cfg = get_config("bert-large")
    shapes = jax.eval_shape(
        lambda: init_params(cfg.model, jax.random.PRNGKey(0),
                            scan_layers=cfg.parallel.scan_layers))
    return ParamLayout.for_tree(shapes)


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


# ---------------------------------------------------------------------------
# attention: forward, backward (via jax.grad through the custom VJP), decode
# ---------------------------------------------------------------------------

ATTN = {
    # (B, S, H, KV, D, causal, packed)
    "bert_implicit": (8, 128, 16, 16, 64, False, False),
    "bert_packed": (8, 128, 16, 16, 64, False, True),
    "granite_gqa_packed": (1, 4096, 32, 8, 64, True, True),
    # MQA 48:1 at D=128: the backward takes the split dk/dv + dq path
    "granite_20b_mqa_packed": (1, 4096, 48, 1, 128, True, True),
}


@pytest.mark.parametrize("name", list(ATTN))
@pytest.mark.parametrize("grad", [False, True], ids=("fwd", "fwd_bwd"))
def test_flash_attention_compiles(one_chip, name, grad):
    from repro.kernels.flash_attention import flash_attention

    b, s, h, kv, d, causal, packed = ATTN[name]

    def attn(q, k, v, pos):
        p = pos if packed else None
        return flash_attention(q, k, v, p, p, causal=causal, interpret=False)

    fn = attn
    if grad:
        fn = jax.grad(lambda *a: attn(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2))
    _compile(one_chip, fn, ((b, s, h, d), BF16), ((b, s, kv, d), BF16),
             ((b, s, kv, d), BF16), ((b, s), I32))


def test_flash_decode_compiles(one_chip):
    from repro.kernels.flash_decode import flash_decode

    b, lanes, cache, h, kv, d = 4, 1, 4096, 32, 8, 64
    fn = functools.partial(flash_decode, interpret=False)
    _compile(one_chip, fn, ((b, lanes, h, d), BF16), ((b, cache, kv, d), BF16),
             ((b, cache, kv, d), BF16), ((b, lanes), I32), ((b, cache), I32),
             ((b, lanes), I32), ((b, cache), I32))


# ---------------------------------------------------------------------------
# the dropless expert layer's grouped matmuls at Mellum2-12B-A2.5B's widths:
# 8 held experts, d_model 2304 <-> expert width 896, the 65,536-row buffer
# of an 8192-token microbatch at top-8
# ---------------------------------------------------------------------------

GMM = {"up": (2304, 896), "down": (896, 2304)}


@pytest.mark.parametrize("name", list(GMM))
@pytest.mark.parametrize("grad", [False, True], ids=("fwd", "fwd_bwd"))
def test_grouped_matmul_compiles(one_chip, name, grad):
    from repro.kernels.grouped_matmul import grouped_matmul

    k, n = GMM[name]

    def fn(x, w, sizes):
        if not grad:
            return grouped_matmul(x, w, sizes)
        return jax.grad(lambda x, w: jnp.sum(grouped_matmul(x, w, sizes).astype(jnp.float32)),
                        argnums=(0, 1))(x, w)

    _compile(one_chip, fn, ((65536, k), BF16), ((8, k, n), BF16), ((8,), I32))


# ---------------------------------------------------------------------------
# flat optimizer updates and gradient statistics at BERT-large's layout
# ---------------------------------------------------------------------------

HYPER = dict(b1=0.9, b2=0.999, b3=0.9, eps=1e-6, wd=0.01, gamma=0.1, gsnr_eps=1e-8)


@pytest.mark.parametrize("name", ["flat_vr_scale", "flat_vr_adam", "flat_vr_lamb",
                                  "flat_vr_lars"])
def test_flat_update_compiles(one_chip, bert_layout, name):
    from repro.kernels import flat_update as fu

    layout = bert_layout
    rows = ((layout.n_rows, LANE), jnp.float32)
    scal = ((1, 8), jnp.float32)
    if name == "flat_vr_scale":
        fn = functools.partial(fu.flat_vr_scale, layout=layout, gamma=0.1, eps=1e-8,
                               interpret=False)
        _compile(one_chip, fn, rows, rows, rows)
    elif name == "flat_vr_lars":
        fn = functools.partial(fu.flat_vr_lars, layout=layout, mu=0.9, wd=0.01,
                               trust=0.001, eps=1e-8, interpret=False)
        _compile(one_chip, fn, rows, rows, rows, rows, rows, scal)
    else:
        fn = functools.partial(getattr(fu, name), layout=layout, interpret=False, **HYPER)
        _compile(one_chip, fn, *[rows] * 7, scal)


@pytest.mark.parametrize("name", ["flat_moments_accum", "flat_moments_finalize",
                                  "flat_pack_square"])
def test_flat_stats_compiles(one_chip, bert_layout, name):
    from repro.kernels import flat_stats as fs

    rows = ((bert_layout.n_rows, LANE), jnp.float32)
    fn = functools.partial(getattr(fs, name), interpret=False)
    if name == "flat_moments_accum":
        _compile(one_chip, fn, rows, rows, rows)
    elif name == "flat_moments_finalize":
        _compile(one_chip, lambda a, b: fn(a, b, 8), rows, rows)
    else:
        _compile(one_chip, fn, rows)


@pytest.mark.parametrize("name", ["leaf_r_partials", "vr_scale_apply", "vr_adam_apply",
                                  "vr_lamb_compute", "vr_lars_compute"])
def test_flat_spmd_compiles(one_chip, bert_layout, name):
    """The per-shard kernels at one shard of a 4-way split of the buffer."""
    from repro.kernels import flat_spmd as fsp

    layout = bert_layout
    local = -(-layout.n_blocks // 4)
    rows = ((local * layout.block_rows, LANE), jnp.float32)
    lids = ((local,), I32)
    acc = ((layout.leaf_slots, LANE), jnp.float32)
    inv = ((layout.leaf_slots, 1), jnp.float32)
    scal = ((1, 8), jnp.float32)
    fn = functools.partial(getattr(fsp, name), layout=layout, interpret=False)
    if name == "leaf_r_partials":
        _compile(one_chip, functools.partial(fn, gsnr_eps=1e-8), rows, rows, lids)
    elif name == "vr_scale_apply":
        _compile(one_chip, functools.partial(fn, gamma=0.1, eps=1e-8),
                 rows, rows, rows, acc, lids, inv)
    elif name == "vr_lars_compute":
        _compile(one_chip, functools.partial(fn, wd=0.01, eps=1e-8),
                 rows, rows, rows, rows, scal, acc, lids, inv)
    else:
        _compile(one_chip, functools.partial(fn, **HYPER),
                 *[rows] * 7, scal, acc, lids, inv)


# ---------------------------------------------------------------------------
# the whole fused train step: the program's named scopes (repro.obs) rename
# no kernel, and each kernel's op_name carries its phase's scope
# ---------------------------------------------------------------------------


def _step_text(one_chip, with_scopes: bool) -> str:
    import contextlib
    from unittest import mock

    from repro import obs
    from repro.backend import Backend
    from repro.configs import Config, ModelConfig, OptimizerConfig, ParallelismConfig
    from repro.train import init_state, make_train_step

    model = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=256, n_heads=4,
                        n_kv_heads=2, d_ff=512, vocab_size=512, head_dim=64,
                        block_pattern=("attn",), norm="rmsnorm", act="swiglu", causal=True,
                        tie_embeddings=True)
    cfg = Config(model=model, optimizer=OptimizerConfig(name="vr_lamb", k=2),
                 parallel=ParallelismConfig(compute_dtype="bfloat16",
                                            backend=Backend.all_fused(interpret=False)),
                 global_batch=4, seq_len=256)
    place = functools.partial(jax.tree_util.tree_map,
                              lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip))
    state = place(jax.eval_shape(lambda: init_state(cfg)))
    batch = place({k: jax.ShapeDtypeStruct((4, 256), I32) for k in ("tokens", "targets")})
    bare = mock.patch.object(obs, "scope", lambda name: contextlib.nullcontext())
    with contextlib.nullcontext() if with_scopes else bare:
        step_fn, _ = make_train_step(cfg)
        return jax.jit(step_fn).lower(state, batch).compile().as_text()


def test_the_scopes_rename_no_kernel_of_the_fused_step(one_chip):
    import re

    from repro import obs

    scoped, bare = _step_text(one_chip, True), _step_text(one_chip, False)
    strip = re.compile(r", metadata=\{[^}]*\}")
    assert ([strip.sub("", x) for x in scoped.splitlines() if " = " in x]
            == [strip.sub("", x) for x in bare.splitlines() if " = " in x])
    kernels = dict(re.findall(
        r'%((?:flash_attention|flat_moments_accum|flat_moments_finalize|flat_vr_lamb)\.\d+) = '
        r'.*?custom-call\(.*op_name="([^"]*)"', scoped))
    by_kernel = {}
    for name, op_name in kernels.items():
        by_kernel.setdefault(name.split(".")[0], []).append(op_name)
    assert len(by_kernel["flash_attention"]) == 3  # forward, recompute, backward
    assert all("(model)" in n for n in by_kernel["flash_attention"])
    assert all(f"/{obs.STATS_ACCUM}/" in n for n in by_kernel["flat_moments_accum"])
    assert all(f"/{obs.STATS_FINALIZE}/" in n for n in by_kernel["flat_moments_finalize"])
    assert all(f"/{obs.OPTIMIZER}/" in n for n in by_kernel["flat_vr_lamb"])


# ---------------------------------------------------------------------------
# the data-axis GSNR statistics across the four chips
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fused", [True, False], ids=("fused", "two"))
def test_data_axis_statistics_reduce_scatter_on_four_chips(four_chips, fused):
    """The [g; g²] exchange compiles to two reduce-scatters into the row
    shards, one of g and one of g², in either schedule, and no all-reduce of
    a flat buffer or more is left.  The buffer is 32 MiB: past 16 MiB the
    compiler lowers a reduce-scatter it cannot keep, such as one over the
    middle dimension of a (2, rows, LANE) payload, as an all-reduce of the
    whole payload and a slice."""
    import re

    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.distributed import device_grad_stats_fn

    n = 8 * 1024 * 1024  # 65,536 flat rows
    params = {"w": jax.ShapeDtypeStruct((n,), jnp.float32,
                                        sharding=NamedSharding(four_chips, P()))}
    batch = jax.ShapeDtypeStruct((4, n), jnp.float32,
                                 sharding=NamedSharding(four_chips, P("data")))
    loss = lambda p, b: jnp.mean(jnp.square(p["w"] - b))
    fn = device_grad_stats_fn(loss, four_chips, fused=fused, flat=True)
    text = jax.jit(fn).lower(params, batch).compile().as_text()
    entry = text[text.index("\nENTRY"):]
    scatters = len(re.findall(r"calls=%all-reduce-scatter| reduce-scatter\(", entry))
    assert scatters == 2, scatters
    reduced = re.findall(r"= f32\[([\d,]+)\]\S* all-reduce(?:-start)?\(", entry)
    full = (n // LANE) * LANE
    assert all(np.prod([int(d) for d in dims.split(",")]) < full for dims in reduced), reduced
