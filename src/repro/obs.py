"""The program's own instrumentation: the names of its phases, spans and
counters, and the helpers that place them.

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
  ``moe``             a dropless expert layer, holding the four       models/moe.py
                      below: ``moe_route`` (router, top-k, losses),
                      ``moe_dispatch`` (rows sorted by expert and
                      gathered), ``moe_experts`` (the grouped
                      matmuls), ``moe_combine`` (weighted scatter)
  ==================  ==============================================  =======

The ``moe`` scopes lie inside ``model`` and are not phases of their own
(``SCOPES`` holds the phases).

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

Counters (:func:`count`) bring a number the step computes on the device to
the host, once a step, through one host callback; a value that arrives
while a profiler trace records is logged (:func:`traced_counts`).

  ==================  ==============================================
  counter             what it counts
  ==================  ==============================================
  ``repro.moe.rows``  (token, choice) rows routed to the experts this
                      program holds, summed over the dropless layers
                      and the microbatches of a step (train/trainer.py)
  ==================  ==============================================
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
MOE = "moe"
MOE_ROUTE = "moe_route"
MOE_DISPATCH = "moe_dispatch"
MOE_EXPERTS = "moe_experts"
MOE_COMBINE = "moe_combine"
MOE_SCOPES = (MOE, MOE_ROUTE, MOE_DISPATCH, MOE_EXPERTS, MOE_COMBINE)

DATA_PRODUCE = "repro.data.produce"
SPANS = (DATA_PRODUCE,)

MOE_ROWS = "repro.moe.rows"
COUNTERS = (MOE_ROWS,)

# traced spans (and counts) kept per name; a process that records more
# keeps the newest
LOG_SIZE = 4096
_LOG: Dict[str, Deque[int]] = {name: collections.deque(maxlen=LOG_SIZE)
                               for name in SPANS + COUNTERS}


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


def count(name: str, value) -> None:
    """Inside a jitted step: sends the scalar ``value`` to the host each
    time the step runs, where it is logged if a profiler trace records."""

    def log(v):
        if jax.profiler.TraceAnnotation.is_enabled():
            _LOG[name].append(int(round(float(v))))

    jax.debug.callback(log, value)


def traced_counts(name: str) -> List[int]:
    """Values of counter ``name`` that arrived while a profiler trace
    recorded, oldest first."""
    return list(_LOG[name])


def traced_durations(name: str) -> List[int]:
    """Durations in ns of this process's spans of ``name`` that started
    while a profiler trace recorded, oldest first."""
    return list(_LOG[name])
