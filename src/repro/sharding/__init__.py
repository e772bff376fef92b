from repro.sharding.rules import (  # noqa: F401
    Rules,
    activate,
    active_rules,
    batch_axes,
    constrain,
    param_pspecs,
    param_shardings,
    pspec_for_leaf,
    rules_for,
)
