"""THE grid index-map walker — one implementation, two consumers.

Walks a Pallas grid in row-major order (last dimension fastest — the Pallas
iteration order), calling each BlockSpec's REAL ``index_map`` with concrete
python ints (plus the concrete scalar-prefetch fetch array where the kernel
uses one).  Everything downstream is a fold over the resulting index
sequence:

  * benchmarks.cost_model counts a DMA exactly when the returned index
    changes vs the previous step (the Mosaic copy-in/copy-out elision rule)
    and turns visits into HBM bytes;
  * repro.analysis.rules detects output revisits (an output block whose
    index recurs in two separate runs), verifies PHASE_WINDOWS parking
    (inputs: constant index outside the declared live window; outputs:
    every run of an index holds a live, written step).

Keeping the walker here (and importing it from cost_model) is an acceptance
criterion of the contract checker: the race detector and the cost model
must replay the same geometry the same way.
"""
from __future__ import annotations

import itertools
import math
from typing import Dict, Iterable, List, Tuple


def grid_steps(grid: Tuple[int, ...]):
    """Row-major iteration over all grid index tuples (last dim fastest)."""
    return itertools.product(*(range(n) for n in grid))


def replay_indices(grid: Tuple[int, ...], spec, extra: Tuple = ()) -> List[tuple]:
    """One operand's ordered block-index sequence over the full grid walk.

    ``extra`` is appended to every index-map call (the flattened
    scalar-prefetch fetch array for the attention kernels' kv maps).
    """
    index_map = spec.index_map
    return [tuple(int(x) for x in index_map(*idx, *extra)) for idx in grid_steps(grid)]


def count_visits(seq: List[tuple]) -> int:
    """Block visits under the Mosaic elision rule: a DMA happens exactly
    when the index differs from the previous grid step."""
    return sum(1 for i, bi in enumerate(seq) if i == 0 or bi != seq[i - 1])


def _blk_bytes(spec, elem_bytes: int) -> int:
    """Bytes of one block window (a None — squeezed — dim counts as 1)."""
    return int(math.prod(1 if d is None else d for d in spec.block_shape)) * elem_bytes


def replay_dma(grid: Tuple[int, ...],
               operands: Iterable[Tuple[str, object, int, bool]],
               extra: Tuple = ()) -> Dict[str, dict]:
    """Per-operand {visits, bytes} over the grid walk.

    operands: (name, BlockSpec, elem_bytes, is_output).  An output costs
    one copy-back per visit: the TPU pipeline never fetches output blocks.
    """
    out = {}
    for name, spec, eb, is_out in operands:
        visits = count_visits(replay_indices(grid, spec, extra))
        out[name] = {
            "visits": visits,
            "bytes": visits * _blk_bytes(spec, eb),
        }
    return out
