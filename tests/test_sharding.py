"""Sharding rules: divisibility-adaptive FSDP+TP, expert parallelism, batch."""
import jax
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.sharding.rules import Rules

POD = AbstractMesh((2, 16, 16), ("pod", "data", "model"))
SINGLE = AbstractMesh((16, 16), ("data", "model"))


def test_generic_weight_fsdp_tp():
    r = Rules(mesh=SINGLE)
    assert r.leaf_pspec("groups/pos0/mlp/wi", (6144, 16384)) == P("data", "model")
    # non-divisible last dim -> replicated on model
    assert r.leaf_pspec("x/w", (6144, 100)) == P("data", None)
    # non-divisible second-to-last -> no fsdp
    assert r.leaf_pspec("x/w", (100, 16384)) == P(None, "model")


def test_stacked_scan_leaves_keep_leading_dim_replicated():
    r = Rules(mesh=SINGLE)
    assert r.leaf_pspec("groups/pos0/attn/wq", (7, 4096, 4096)) == P(None, "data", "model")


def test_expert_parallel_when_divisible():
    r = Rules(mesh=SINGLE)
    # llama4: 128 experts over 16-way model axis
    assert r.leaf_pspec("moe/expert_wi", (128, 5120, 8192)) == P("model", "data", None)
    # mixtral: 8 experts do NOT divide 16 -> TP inside expert instead
    assert r.leaf_pspec("moe/expert_wi", (8, 6144, 16384)) == P(None, "data", "model")


def test_embedding_vocab_sharding():
    r = Rules(mesh=SINGLE)
    assert r.leaf_pspec("embed/embed", (32768, 4096)) == P("model", "data")
    # whisper vocab 51865 not divisible -> replicate vocab, fsdp features
    assert r.leaf_pspec("embed/embed", (51865, 768)) == P(None, "data")


def test_small_vectors_replicated():
    r = Rules(mesh=SINGLE)
    assert r.leaf_pspec("final_norm/scale", (4096,)) == P(None)


def test_batch_axes_adaptive():
    r1 = Rules(mesh=SINGLE)
    assert r1.batch_axes(256) == "data"
    assert r1.batch_axes(1) is None  # long_500k: batch cannot shard
    r2 = Rules(mesh=POD)
    assert r2.batch_axes(256) == ("pod", "data")
    assert r2.batch_axes(2) == "pod"
    assert r2.batch_axes(3) is None


def test_fsdp_off():
    r = Rules(mesh=SINGLE, fsdp=False)
    assert r.leaf_pspec("mlp/wi", (4096, 16384)) == P(None, "model")


def test_cache_seq_fallback_spec():
    from repro.launch.specs import batch_pspec

    # paper-faithful fallback (cache_seq_tp off): batch 1 -> seq over data only
    r_off = Rules(mesh=SINGLE, cache_seq_tp=False)
    leaf = jax.ShapeDtypeStruct((1, 524288, 1, 128), "float32")
    assert batch_pspec(leaf, r_off, 1, kind="cache") == P(None, "data", None, None)
    leaf2 = jax.ShapeDtypeStruct((128, 32768, 8, 128), "float32")
    assert batch_pspec(leaf2, r_off, 128, kind="cache") == P("data", None, None, None)
    # stacked scan cache (groups, B, C, kv, hd): batch located at dim 1
    leaf3 = jax.ShapeDtypeStruct((24, 128, 32768, 8, 128), "float32")
    assert batch_pspec(leaf3, r_off, 128, kind="cache") == P(None, "data", None, None, None)
    # cache_tp (the §Perf-accepted default): seq dim takes the leftover model
    # axis (flash-decode layout)
    r_tp = Rules(mesh=SINGLE)
    assert r_tp.cache_seq_tp
    assert batch_pspec(leaf3, r_tp, 128, kind="cache") == P(None, "data", "model", None, None)
    assert batch_pspec(leaf2, r_tp, 128, kind="cache") == P("data", "model", None, None)
    # cache_tp at batch 1: seq shards over BOTH axes
    assert batch_pspec(leaf, r_tp, 1, kind="cache") == P(None, ("data", "model"), None, None)


def test_flat_buffer_rows_fsdp():
    """Packed (rows, 128) optimizer buffers shard the ROWS dim over the FSDP
    axes; the lane dim stays whole (the generic 2-D rule would TP-shard it)."""
    import jax.numpy as jnp

    from repro.core.layout import FlatBuffer, ParamLayout, is_flat
    from repro.sharding.rules import param_pspecs

    r = Rules(mesh=SINGLE)
    assert r.flat_buffer_pspec((512, 128)) == P("data", None)
    # the generic rule WOULD have hit this shape with P("data", "model")
    assert r.leaf_pspec("m/data", (512, 128)) == P("data", "model")
    # fsdp off / non-divisible rows -> replicated
    assert Rules(mesh=SINGLE, fsdp=False).flat_buffer_pspec((512, 128)) == P(None, None)
    assert r.flat_buffer_pspec((7, 128)) == P(None, None)
    # pod meshes follow the fsdp_over_pod knob like every other weight
    rp = Rules(mesh=POD, fsdp_over_pod=True)
    assert rp.flat_buffer_pspec((512, 128)) == P(("pod", "data"), None)

    # through param_pspecs the FlatBuffer node structure is preserved and the
    # spec rides inside it (64 rows divide the 16-way data axis)
    tree = {"w": jnp.ones((40, 7))}
    layout = ParamLayout.for_tree(tree)
    fb = FlatBuffer(layout.pack(tree), layout)
    specs = param_pspecs({"m": fb, "step": jnp.zeros((), jnp.int32)}, r)
    assert is_flat(specs["m"])
    assert specs["m"].data == P("data", None)
    assert specs["step"] == P()


def test_constrain_noop_without_mesh():
    import jax.numpy as jnp

    from repro.sharding.rules import constrain

    x = jnp.ones((4, 4))
    assert constrain(x, ("batch", None)) is x
