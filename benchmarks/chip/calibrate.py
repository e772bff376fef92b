"""Readings that the limits of ``correct`` are set from (``limits/<cell>.json``).

  python3 benchmarks/chip/calibrate.py --workload <cell> --seeds 1 2 ... [--controls 3]
  python3 benchmarks/chip/calibrate.py --workload <cell> --seeds 1 2 3 --plant no_exchange

For each seed, in one process and with one compile: the program's checked
steps through the timed call and feed, the plain reference, and for the first
``--controls`` seeds the control (the reference with float8 matmuls in the
program's place) and the faults planted in the reference put in the
program's place (``reference.FAULTS``: half of the batch left out, the
batches' second half repeating the first; the mean of squares replaced by
the square of the mean; the last microbatch's square left out of the sum).
Prints one JSON line per seed with the numbers of ``check.py`` for each:
the lower reading of a
limit is the largest the program gives, the upper the least that the control
or a fault gives.  ``--plant`` plants a fault in the program instead
(``PLANTED``: on a cell on several chips, the exchange of the GSNR
statistics between the chips left out, so that each chip keeps its own) and
prints the faulty program's numbers.  The benchmark's runs never call this.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import shutil
import sys
import time

import run  # noqa: F401  (puts the repository and the program on sys.path)


def _no_exchange():
    """Every ``pmean`` (the data-axis statistics' one collective, and the
    loss's) returns each chip's own value."""
    import jax
    from unittest import mock

    return mock.patch.object(jax.lax, "pmean", lambda x, axis_name, **kw: x)


PLANTED = {"no_exchange": _no_exchange}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--plant", choices=sorted(PLANTED))
    args = ap.parse_args(argv)
    plant = PLANTED[args.plant] if args.plant else contextlib.nullcontext

    from benchmarks.chip import check, program, reference, spec

    cell = spec.load_cell(run.ROOT, args.workload)
    run.chips_for(cell.chips, require_chip=True)
    run.compile_cache(cell.bench_dir)
    conf, traffic = cell.config, cell.traffic
    n = int(traffic["checked_steps"])
    compiled = None
    for i, seed in enumerate(args.seeds):
        t = time.perf_counter()
        with plant():
            su = run.prepare(cell, seed, compiled=compiled)
        compiled = su.compiled
        prog = run.host_readings(run.checked_steps(su, conf, seed, n))
        su.it.close()
        su.state = su.it = None
        gc.collect()
        host = program.host_batches(program.dataset(str(su.cache_dir), traffic), n)
        shutil.rmtree(su.cache_dir, ignore_errors=True)
        t_prog = time.perf_counter() - t
        ref = reference.readings(conf, traffic, seed, host)
        t_ref = time.perf_counter() - t - t_prog
        out = {"seed": seed, args.plant or "program": check.numbers(prog, ref),
               "loss": prog["loss"], "ref_loss": ref["loss"],
               "seconds": {"program": t_prog, "reference": t_ref}}
        if i < args.controls and not args.plant:
            out["control"] = check.numbers(
                reference.readings(conf, traffic, seed, host, quant=True), ref)
            for fault in reference.FAULTS:
                out[fault] = check.numbers(
                    reference.readings(conf, traffic, seed, host, fault=fault), ref)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
