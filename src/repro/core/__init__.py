from repro.core.accumulate import grad_only, grad_stats, split_batch  # noqa: F401
from repro.core.baselines import Transform, adam, lamb, lars, momentum, sgd  # noqa: F401
from repro.core.distributed import device_grad_stats_fn  # noqa: F401
from repro.core.layout import (  # noqa: F401
    FlatBuffer,
    ParamLayout,
    as_flat,
    is_flat,
    unpack_tree,
)
from repro.core.gsnr import (  # noqa: F401
    GradStats,
    clip_ratio,
    gsnr_scale,
    gsnr_summary,
    normalize_per_layer,
    raw_gsnr,
    stats_summary,
    variance,
)
from repro.core.noise_scale import (  # noqa: F401
    NoiseScaleEstimate,
    NoiseScaleState,
    estimate as estimate_noise_scale,
    noise_terms,
)
from repro.core.schedule import linear_scaled_lr, make_schedule, scaled_lr, sqrt_scaled_lr  # noqa: F401
from repro.core.vrgd import (  # noqa: F401
    make_optimizer,
    vr_adam,
    vr_lamb,
    vr_lars,
    vr_momentum,
    vr_sgd,
)
