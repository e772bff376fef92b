"""Data path: mean host time the prefetch producer takes for one batch
(gather from the memmap cache, place on the device), over the program's
``repro.data.produce`` spans that started while the profiler recorded
(``obs.traced_durations``; run.py traces only the window), in ms.  Per
span, not per step: the producer runs up to three batches ahead."""
from benchmarks.chip import scopes


def read(run):
    obs = scopes.program_obs()
    if run.trace is None or obs is None:
        return None
    spans = obs.traced_durations(obs.DATA_PRODUCE)
    return sum(spans) * 1e-6 / len(spans) if spans else None
