"""Roofline terms per (arch x shape) from the dry-run artifacts.

Reads experiments/dryrun/*.json (produced by ``python -m repro.launch.dryrun
--all``) and emits one CSV row per combination — the §Roofline table's data.
It starts no dry-run itself: the dry-run needs 512 fake CPU devices fixed
before JAX starts, and this process has started JAX already (and may hold a
chip).  Run the dry-run first, in its own process.
"""
from __future__ import annotations

import glob
import os
import time

from benchmarks.common import emit
from repro.launch.roofline import load, terms


def main(fast: bool = False) -> None:
    t0 = time.time()
    d = "experiments/dryrun"
    if not glob.glob(os.path.join(d, "*.json")):
        raise FileNotFoundError(
            f"no dry-run artifacts under {d}/ — run `PYTHONPATH=src python -m "
            "repro.launch.dryrun --arch whisper-small --shape decode_32k` first")
    for rec in load(d):
        name = f"roofline_{rec['arch']}_{rec['shape']}_{rec['mesh']}"
        if rec.get("skipped"):
            emit(name, 0.0, f"skip={rec['skipped']}")
            continue
        if not rec.get("ok"):
            emit(name, 0.0, f"fail={rec.get('error','')[:50]}")
            continue
        t = terms(rec)
        emit(
            name,
            t["step_time_lb"] * 1e6,  # lower-bound step time from the dominant term
            f"dom={t['dominant']};compute_ms={t['compute']*1e3:.2f};"
            f"mem_ms={t['memory']*1e3:.2f};coll_ms={t['collective']*1e3:.2f};"
            f"useful={t['useful_ratio']:.2f}",
        )
    print(f"# bench_roofline done in {time.time()-t0:.1f}s")


if __name__ == "__main__":
    main()
