"""The program's own instrumentation: the names of its phases and two helpers.

Scopes (:func:`scope`, ``jax.named_scope``) name the phases of the train
step.  Each adds a component to the ``op_name`` metadata of every HLO
instruction traced inside it and changes nothing else: no operation, no
instruction name.  Autodiff keeps the scope and marks the phase around it,
so under ``MODEL`` the forward reads ``.../jvp(model)/...``, the backward
``.../transpose(jvp(model))/...`` and the recompute of a ``jax.checkpoint``
``.../rematted_computation/...``.  A profile's device ops name their HLO
instruction; the compiled step's text maps that name to its ``op_name``.

  ==================  ==============================================  =======
  scope               what it holds                                   placed in
  ==================  ==============================================  =======
  ``model``           forward and loss (and so backward, recompute)   train/loss.py
  ``stats_pack``      the gradient tree packed into the flat buffer   kernels/ops.py
  ``stats_accum``     the moment accumulation kernels                 kernels/ops.py
  ``stats_finalize``  the /k normalize of the moments                 kernels/ops.py
  ``optimizer``       grad norm, clip, VR update, unpack and apply    train/trainer.py
  ==================  ==============================================  =======

Spans (:func:`span`, ``jax.profiler.TraceAnnotation``) time host work on the
profiler's clock; every span's name starts with ``repro.``.  A span that
starts while a profiler trace records also logs its duration in this
process (:func:`traced_durations`), so that a reader holding no trace sees
the spans the trace holds.

  ======================  ==============================================
  span                    what it holds
  ======================  ==============================================
  ``repro.data.produce``  one prefetched batch: gathered and placed
                          (data/memmap.py, the prefetch thread)
  ======================  ==============================================
"""
from __future__ import annotations

import collections
import contextlib
import time
from typing import Deque, Dict, Iterator, List

import jax

MODEL = "model"
STATS_PACK = "stats_pack"
STATS_ACCUM = "stats_accum"
STATS_FINALIZE = "stats_finalize"
OPTIMIZER = "optimizer"
SCOPES = (MODEL, STATS_PACK, STATS_ACCUM, STATS_FINALIZE, OPTIMIZER)

DATA_PRODUCE = "repro.data.produce"
SPANS = (DATA_PRODUCE,)

# traced spans kept per name; a process that records more keeps the newest
LOG_SIZE = 4096
_LOG: Dict[str, Deque[int]] = {name: collections.deque(maxlen=LOG_SIZE) for name in SPANS}


def scope(name: str):
    """Names the ops traced inside it (HLO metadata only, no runtime cost)."""
    return jax.named_scope(name)


@contextlib.contextmanager
def span(name: str) -> Iterator[None]:
    """Times the host work inside it on the profiler's clock; logs its
    duration if a profiler trace records when it starts."""
    traced = jax.profiler.TraceAnnotation.is_enabled()
    t0 = time.perf_counter_ns()
    with jax.profiler.TraceAnnotation(name):
        yield
    if traced:
        _LOG[name].append(time.perf_counter_ns() - t0)


def traced_durations(name: str) -> List[int]:
    """Durations in ns of this process's spans of ``name`` that started
    while a profiler trace recorded, oldest first."""
    return list(_LOG[name])
