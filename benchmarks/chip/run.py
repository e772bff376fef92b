"""One run of one benchmark cell on the chips of this machine.

  python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up makes the cell's corpus from the seed and writes it through the
program's token cache, makes the weights on the device from the seed, builds
the program's jitted ``make_train_step`` with its state, compiles it and
drives it through the first steps that the reference checks, on batches from
``IndexedPackedDataset.iter_batches(device=True, prefetch_size=2)``.  A cell
on several chips builds the program's data mesh over them, shards the state
as the program does and feeds batches split by rows from the program's
``device_prefetch`` (``program.py``).  The
same compiled step, state and batch feed then run the measured window: steps
are dispatched back to back, at most ``IN_FLIGHT`` of them ahead of the
device, with no device value read until the last step has finished.  With
``--trace 0`` the window lasts ``--seconds`` and the end-to-end metrics are
reported; with ``--trace 1`` a profiler trace of ``trace_steps`` steps gives
the per-layer metrics.  Either way, once the window has closed and the
program's state is freed, the plain reference (``reference.py``) runs the
checked steps again and ``check.py`` decides ``correct``.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``: each number compared with its limit,
which are also the last lines of standard error.  Without a TPU, or with
fewer chips than the cell asks for, it prints no result and exits non-zero.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Callable, Dict, Optional  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[2]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

NO_CHIP_EXIT = 3
# steps dispatched ahead of the device: a real loop keeps a couple in flight
IN_FLIGHT = 2
# the TPU runtime would otherwise log to a fixed directory under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")


class NoChip(RuntimeError):
    pass


@dataclasses.dataclass
class RunInfo:
    """What a metric's reader gets (``metrics/<name>.py``: ``read(run)``)."""
    conf: Dict
    traffic: Dict
    chips: int
    peak: Optional[Dict]
    itemsize: int
    setup_s: float
    window_s: Optional[float] = None  # host clock, --trace 0
    window_steps: int = 0
    window_tokens: int = 0
    trace: object = None  # xplane.Trace, --trace 1
    traced_steps: int = 0
    traced_tokens: int = 0
    traced_pairs: object = 0  # what the family's attention counts take (work.pairs)
    traced_pieces: object = None  # trained lengths of the traced document pieces
    op_scopes: Optional[Dict[str, str]] = None  # the traced step's op_names (scopes.op_scopes)


@dataclasses.dataclass
class Setup:
    cfg: object
    it: object
    state: object
    compiled: object
    cache_dir: pathlib.Path
    phases: Dict[str, float]  # seconds of each part of set-up
    mesh: object = None  # the program's mesh of a cell on several chips


def chips_for(chips: int, require_chip: bool):
    import jax

    devices = jax.devices()
    if require_chip:
        if devices[0].platform != "tpu":
            raise NoChip(f"no TPU: JAX's first device is {devices[0].platform!r}")
        if len(devices) < chips:
            raise NoChip(f"the cell asks for {chips} chips, JAX finds {len(devices)}")
    return devices[:chips]


def compile_cache(bench_dir: pathlib.Path) -> None:
    """JAX's persistent cache at a fixed path inside the checkout, holding
    every program, so that only a checkout's first run compiles."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(bench_dir / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def prepare(cell, seed: int, wrap_step: Optional[Callable] = None, compiled=None) -> Setup:
    """Corpus, dataset, weights, state and the compiled step of one seed."""
    from benchmarks.chip import program, weights

    conf, traffic = cell.config, cell.traffic
    phases, t = {}, time.perf_counter()

    def lap(name):
        nonlocal t
        now = time.perf_counter()
        phases[name], t = now - t, now

    cfg = program.train_config(conf, traffic)
    mesh = program.make_mesh(cell.chips)
    cache_dir = cell.bench_dir / ".corpus" / cell.name
    program.write_corpus(traffic, int(conf["vocab_size"]), seed, cache_dir)
    lap("corpus")
    ds = program.dataset(str(cache_dir), traffic)
    for epoch in range(2):  # both epochs' pack indices are built here, never in a window
        ds.pack_for(epoch)
    lap("pack_index")
    state = program.init_state(cfg, weights.make_params(conf, seed), conf, mesh)
    it = program.feed(ds, mesh)
    lap("weights_state")
    if compiled is None:
        compiled = program.make_step(cfg, wrap_step, mesh, state).lower(
            state, program.batch_shapes(traffic, mesh)).compile()
    lap("compile")
    return Setup(cfg, it, state, compiled, cache_dir, phases, mesh)


def checked_steps(su: Setup, conf: Dict, seed: int, n: int) -> Dict:
    """The first ``n`` steps through the window's own call and feed; returns
    the program's readings (device arrays) for the comparison."""
    import jax

    from benchmarks.chip import program, weights

    losses, first = [], None
    for i in range(n):
        su.state, m = su.compiled(su.state, next(su.it))
        losses.append(m["loss"])
        if i == 0:
            first = program.first_step_readings(su.cfg, conf)(su.state)
    bp0 = program.replicate(weights.make_params(conf, seed), su.mesh)
    change = program.change_norms(su.state.params, bp0, conf)
    del bp0
    jax.block_until_ready((su.state, change))
    return {"loss": losses, **first, "change": change}


def host_readings(prog: Dict) -> Dict:
    import jax

    from benchmarks.chip.weights import per_layer

    out = {k: per_layer(v) for k, v in prog.items() if k != "loss"}
    out["loss"] = [float(x) for x in jax.device_get(prog["loss"])]
    return out


def drive(su: Setup, *, seconds: Optional[float] = None, steps: Optional[int] = None):
    """The measured window.  Returns (steps run, start, end) on the host clock."""
    import jax
    from jax.profiler import TraceAnnotation

    pending = collections.deque()
    n = 0
    with TraceAnnotation("window"):
        t_start = time.perf_counter()
        while True:
            with TraceAnnotation("data_wait"):
                batch = next(su.it)
            with TraceAnnotation("dispatch"):
                su.state, metrics = su.compiled(su.state, batch)
            del batch
            n += 1
            pending.append(metrics["loss"])
            if len(pending) > IN_FLIGHT:
                with TraceAnnotation("sync"):
                    pending.popleft().block_until_ready()
            if steps is not None and n >= steps:
                break
            if seconds is not None and time.perf_counter() - t_start >= seconds:
                break
        with TraceAnnotation("sync"):
            jax.block_until_ready(su.state)
        t_end = time.perf_counter()
    return n, t_start, t_end


def _trace_window(su: Setup, cell, steps: int):
    import jax

    from benchmarks.chip import xplane

    trace_dir = cell.bench_dir / ".trace" / cell.name
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        n, _, _ = drive(su, steps=steps)
    finally:
        jax.profiler.stop_trace()
    tr = xplane.load(xplane.find_xplane(str(trace_dir)))
    shutil.rmtree(trace_dir, ignore_errors=True)
    return n, tr


def run_cell(root, name: str, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, wrap_step: Optional[Callable] = None):
    """One run; returns (result object, lines for standard error).
    ``require_chip=False`` skips the look for a chip (tests on the CPU only)."""
    import jax

    from benchmarks.chip import check, program, reference, scopes, spec, work
    from benchmarks.chip import xplane
    from benchmarks.chip.peaks import peaks

    cell = spec.load_cell(pathlib.Path(root), name)
    devices = chips_for(cell.chips, require_chip)
    if require_chip:
        compile_cache(cell.bench_dir)
    conf, traffic = cell.config, cell.traffic
    n_checked = int(traffic["checked_steps"])
    rows = int(traffic["rows"])

    t_start = time.perf_counter()
    su = prepare(cell, seed, wrap_step)
    prog = checked_steps(su, conf, seed, n_checked)
    setup_s = time.perf_counter() - T0
    su.phases = {"start": t_start - T0, **su.phases,
                 "checked_steps": time.perf_counter() - t_start - sum(su.phases.values())}
    info = RunInfo(conf=conf, traffic=traffic, chips=cell.chips,
                   peak=peaks(devices[0].device_kind) if require_chip else None,
                   itemsize=program.compute_itemsize(conf), setup_s=setup_s)
    if trace:
        info.traced_steps, info.trace = _trace_window(su, cell, int(traffic["trace_steps"]))
        info.op_scopes = scopes.op_scopes(su.compiled.as_text())
        measured = info.traced_steps
    else:
        n, t_start, t_end = drive(su, seconds=seconds)
        info.window_steps, info.window_s, measured = n, t_end - t_start, n
    memory = max([program.memory_bytes(su.compiled)]
                 + [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices])
    prog = host_readings(prog)
    su.it.close()
    su.state = su.compiled = su.it = None
    gc.collect()

    counter = program.dataset(str(su.cache_dir), traffic)
    lo, hi = n_checked * rows, (n_checked + measured) * rows
    pieces = program.piece_lengths(counter, lo, hi)
    if trace:
        info.traced_tokens = int(pieces.sum())
        info.traced_pieces = pieces
        info.traced_pairs = work.pairs(conf, pieces)
    else:
        info.window_tokens = int(pieces.sum())
    ref = reference.readings(conf, traffic, seed, program.host_batches(counter, n_checked))
    shutil.rmtree(su.cache_dir, ignore_errors=True)
    correct, checks, lines = check.judge(check.numbers(prog, ref), cell.limits)

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = m.read(info)
        if v is not None:
            metrics[m.name] = {"value": v, "unit": m.unit}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory}
    result = {"correct": bool(correct), "attempted": measured, "failed": 0,
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = xplane.mean_busy_s(info.trace)
        device["window_s"] = info.trace.window_s
        result["breakdown"] = {"device_ops": xplane.top_ops(info.trace),
                               "idle_gaps": xplane.idle_gaps(info.trace)}
    result["checks"] = checks
    notes = [f"attempted {measured} device {device}",
             "setup phases (s) " + " ".join(f"{k} {v:.2f}" for k, v in su.phases.items())]
    return result, notes + lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result, lines = run_cell(ROOT, args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"run.py: {e}; this benchmark runs only on the chip", file=sys.stderr)
        return NO_CHIP_EXIT
    for line in lines:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
