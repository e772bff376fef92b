# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness — one module per paper table/figure:

  bench_data         indexed-cache data path (build cost, gather vs re-pack)
  bench_linreg       Fig. 5 (convergence) + Fig. 4 (gamma/k sensitivity)
  bench_cifar_proxy  Table 6 / Fig. 3 (LB ablation across 4 optimizer pairs)
  bench_bert_proxy   Table 1 (pretraining quality vs batch, LAMB vs VR-LAMB)
  bench_gengap       Tables 2 & 4 (generalization gap)
  bench_dlrm_proxy   Table 5 (CTR AUC vs batch, SGD vs VR-SGD)
  bench_overhead     VRGD systems cost (step overhead + fused kernel)
  bench_serve        continuous-batching serving (mixed prefill/decode)
  bench_roofline     §Roofline terms from the dry-run artifacts (only on
                     request: it reads what a dry-run wrote)

``python -m benchmarks.run``            full pass (CPU, ~15 min)
``python -m benchmarks.run --fast``     reduced sweeps (~4 min)
``python -m benchmarks.run --only linreg,gengap``
``python -m benchmarks.run --check-regression``  structural cost gate only

The roofline table takes two steps, each its own process: the dry-run
first (it fixes 512 fake CPU devices before JAX starts), then the table::

  PYTHONPATH=src python -m repro.launch.dryrun --arch whisper-small --shape decode_32k
  python -m benchmarks.run --only roofline
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

MODULES = [
    "data",
    "linreg",
    "cifar_proxy",
    "bert_proxy",
    "gengap",
    "dlrm_proxy",
    "overhead",
    "serve",
]
ON_REQUEST = ["roofline"]  # run only when named in --only

_HERE = os.path.dirname(__file__)
BENCH_JSONS = [
    os.path.join(_HERE, "..", "BENCH_flat_state.json"),
    os.path.join(_HERE, "..", "BENCH_serve.json"),
    os.path.join(_HERE, "..", "BENCH_autoscale.json"),
    os.path.join(_HERE, "..", "BENCH_data.json"),
]


def validate_bench_plans() -> bool:
    """Post-run gate: every ``plan`` marker inside each machine-readable
    record file must agree (one resolved Backend per record file), and every
    ``config`` marker must agree key-wise (shapes/optimizer/dtype) — a record
    mixing, say, a TPU fused rerun with leftover CPU-interpret sub-records,
    or an S=256 fast sweep with an S=512 cost record, is refused here even
    if it was hand-assembled rather than merged through common.py."""
    from benchmarks.common import check_configs_agree, check_plans_agree

    ok = True
    for path in BENCH_JSONS:
        if not os.path.exists(path):
            continue
        with open(path) as f:
            rec = json.load(f)
        for check in (check_plans_agree, check_configs_agree):
            try:
                check(rec, what=os.path.basename(path))
            except ValueError as e:
                print(f"# {e}", file=sys.stderr)
                ok = False
    return ok


def check_regression() -> int:
    """``--check-regression``: recompute the structural cost model (pure
    host arithmetic — replays index maps, runs no kernels) at the COMMITTED
    config and fail if the counted hbm_bytes_per_step / mxu_flops_per_step
    regressed >5% vs BENCH_flat_state.json, or if the PR's claimed
    reductions (fused-backward recompute MXU, phase-aware update DMA) no
    longer hold.  Wired into the verify skill so a grid/index-map change
    that silently reintroduces DMA or recompute fails pre-merge."""
    from benchmarks import cost_model

    path = BENCH_JSONS[0]
    if not os.path.exists(path):
        print(f"# {os.path.basename(path)} missing — run benchmarks first",
              file=sys.stderr)
        return 1
    with open(path) as f:
        committed = json.load(f)
    failures = cost_model.check_regression(committed)
    for msg in failures:
        print(f"# REGRESSION: {msg}", file=sys.stderr)

    # the kernel contract checker rides the same gate: the cost model and
    # the analyzer replay the SAME registered geometries (analysis.replay),
    # so a BlockSpec change that passes the byte counts but breaks a layout
    # / revisit / fetch / VMEM contract still fails here.  Fast mode:
    # representative configs, no launch tracing (the full pass runs in
    # tests/test_analysis.py and `python -m repro.analysis.check`).
    from repro.analysis.check import run_checks

    contract_findings = run_checks(fast=True)
    for f in contract_findings:
        print(f"# CONTRACT: {f}", file=sys.stderr)
        failures.append(str(f))
    if not contract_findings:
        print("# kernel contract check OK (fast pass)")

    if not failures:
        fresh = committed["cost_model"]
        print("# cost-model regression check OK "
              f"(hbm_bytes_per_step={fresh['hbm_bytes_per_step']:,}, "
              f"mxu_flops_per_step={fresh['mxu_flops_per_step']:,})")
    return 1 if failures else 0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--only", default="")
    ap.add_argument(
        "--check-regression", action="store_true",
        help="structural cost-model gate vs committed BENCH_flat_state.json "
             "(no benchmarks are run)",
    )
    args = ap.parse_args()
    if args.check_regression:
        sys.exit(check_regression())
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    only = [s.strip() for s in args.only.split(",") if s.strip()]
    unknown = set(only) - set(MODULES + ON_REQUEST)
    if unknown:
        ap.error(f"unknown benchmark modules: {sorted(unknown)}")
    print("name,us_per_call,derived")
    t0 = time.time()
    failures = []
    for mod in only or MODULES:
        try:
            m = __import__(f"benchmarks.bench_{mod}", fromlist=["main"])
            m.main(fast=args.fast)
        except Exception:  # noqa: BLE001 — keep the harness running
            failures.append(mod)
            print(f"# bench_{mod} FAILED:", file=sys.stderr)
            traceback.print_exc()
    if not validate_bench_plans():
        failures.append("bench_plan_consistency")
    print(f"# total {time.time()-t0:.1f}s; failures: {failures or 'none'}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
