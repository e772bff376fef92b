# Pallas TPU kernels (interpret-mode validated on CPU by tests/oracle.py;
# main-path kernels compile-tested for TPU v5e by tests/test_tpu_compile.py):
#
# Flat-buffer path (the dispatch target — ONE pallas_call per optimizer step
# over the ParamLayout flat buffer, core/layout.py):
#   flat_update.flat_vr_scale   — 2-phase fused GSNR pipeline (VR-SGD/Momentum)
#   flat_update.flat_vr_adam    — 2-phase full VR-Adam step (r-mean in-grid)
#   flat_update.flat_vr_lamb    — 2-phase VR-LAMB + in-grid trust-ratio norms
#   flat_update.flat_vr_lars    — 2-phase VR-LARS + in-grid trust-ratio norms
#   flat_stats.flat_moments_*   — flat k-group moment accumulation/finalize
#   flat_stats.flat_pack_square — the data-parallel [g; g²] payload
#   flat_spmd.*                 — the per-shard pipelines (backend.FlatSpmd)
#
#   flash_attention             — causal/sliding-window online-softmax attention
#                                 (position/segment-aware: packed + offset
#                                 layouts; custom VJP -> fused fwd AND bwd)
#   flash_attention_bwd         — FA-2 recomputation backward (dq, fused dk/dv)
# ops.py holds the jit'd dispatch wrappers; ref.py the pure-jnp oracles.
