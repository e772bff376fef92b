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
#   flat_stats.flat_vmap_moments— batched (k, param) stack -> moments
#
# Per-leaf kernels (PR 1; retained as differential oracle references):
#   vr_update.vr_scale          — fused GSNR pipeline, one tensor
#   vr_adam.vr_adam_inner       — fused VR-Adam inner step, one tensor
#   vr_lamb.vr_lamb_inner       — fused VR-LAMB + norm partials, one tensor
#   vr_lamb.vr_lars_inner       — fused VR-LARS + norm partials, one tensor
#   grad_stats.moments_*        — per-leaf moment accumulation (scan body)
#
#   flash_attention             — causal/sliding-window online-softmax attention
#                                 (position/segment-aware: packed + offset
#                                 layouts; custom VJP -> fused fwd AND bwd)
#   flash_attention_bwd         — FA-2 recomputation backward (dq, fused dk/dv)
# ops.py holds the jit'd dispatch wrappers; ref.py the pure-jnp oracles.
