"""ParamLayout: one packed flat buffer for the whole parameter pytree.

The per-leaf Pallas dispatch (PR 1) pads/unpads every leaf around every
kernel call and pays one kernel launch per leaf — pure DMA and launch
overhead at BERT/DLRM scale, where the optimizer step itself becomes a
wall-clock factor (LAMB, You et al. 2019).  This module flattens a pytree
ONCE into a single ``(n_rows, LANE)`` f32-tile-aligned buffer so the entire
VRGD update (and the GradStats carry) is a single ``pallas_call`` over a
grid of rows.

Layout invariants (what TPU Mosaic validation relies on — see
docs/flat_state.md):

  * every leaf occupies a contiguous run of rows, zero-padded at the tail;
  * each leaf's row count is a multiple of ``block_rows`` (itself a multiple
    of the 8-row f32 sublane), so every ``(block_rows, LANE)`` grid block
    belongs to exactly ONE leaf — per-leaf ("layer") reductions can then
    accumulate into a scratch row indexed by the block's leaf id;
  * the zero padding is preserved by every kernel's element-wise math for
    the streams that matter (g = g2 = w = 0 in the tail), so in-kernel norm
    and mean reductions are exact without masking.

``FlatBuffer`` wraps (buffer, layout) as a registered pytree node: all the
element-wise ``tree_map`` optimizer math in core/vrgd.py runs unchanged on
flat state, scan carries and jit boundaries see a stable treedef, and
checkpointing unpacks back to the plain pytree format at the save/restore
boundary (train/checkpoint.py) so flat and pytree checkpoints interoperate.

Layout equality/hash is *geometry only* (treedef, shapes, block_rows): a
layout built from f32 gradients and one built from bf16 params interoperate
as long as the tree structure matches.  Stored dtypes are kept for
reference; ``unpack`` defaults to the buffer's dtype.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any

# tile geometry from the shared layout-contract constants (LAYOUT-SUBLANE:
# the sublane count is dtype-derived, 8 only for the f32 buffers used here)
from repro.analysis.layout_contracts import LANE, sublane

SUBLANE = sublane(np.float32)  # f32 sublane (second-to-last-dim tile)
FLAT_BLOCK_ROWS = 64  # rows per grid block: (64, 128) f32 = 32 KiB per ref


def _leaf_rows(size: int, block_rows: int) -> int:
    """Rows a ``size``-element leaf occupies: ceil(size/LANE) rounded up to a
    whole number of blocks (so no grid block straddles two leaves)."""
    rows = -(-max(size, 1) // LANE)
    return -(-rows // block_rows) * block_rows


_LAYOUT_CACHE: dict = {}


@dataclasses.dataclass(frozen=True)
class ParamLayout:
    """Static flat-buffer layout for one pytree structure.

    Hashable (usable as a jit static argument and as FlatBuffer treedef
    metadata).  Equality is geometry only — ``dtypes`` is bookkeeping.
    """

    treedef: Any
    shapes: Tuple[Tuple[int, ...], ...]
    block_rows: int = FLAT_BLOCK_ROWS
    dtypes: Tuple[str, ...] = dataclasses.field(default=(), compare=False)
    # derived geometry (functions of the compare fields)
    sizes: Tuple[int, ...] = dataclasses.field(init=False, compare=False, repr=False, default=())
    leaf_rows: Tuple[int, ...] = dataclasses.field(init=False, compare=False, repr=False, default=())
    row_offsets: Tuple[int, ...] = dataclasses.field(init=False, compare=False, repr=False, default=())

    def __post_init__(self):
        if self.block_rows % SUBLANE:
            raise ValueError(f"block_rows={self.block_rows} must be a multiple of the {SUBLANE}-row f32 sublane")
        sizes = tuple(int(np.prod(s, dtype=np.int64)) if len(s) else 1 for s in self.shapes)
        rows = tuple(_leaf_rows(n, self.block_rows) for n in sizes)
        offs, acc = [], 0
        for r in rows:
            offs.append(acc)
            acc += r
        object.__setattr__(self, "sizes", sizes)
        object.__setattr__(self, "leaf_rows", rows)
        object.__setattr__(self, "row_offsets", tuple(offs))

    # -- construction -------------------------------------------------------

    @classmethod
    def for_tree(cls, tree: PyTree, block_rows: int = FLAT_BLOCK_ROWS) -> "ParamLayout":
        """Layout for ``tree``'s structure (cached: repeated calls on the same
        structure — every train step — return the same object)."""
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        shapes = tuple(tuple(jnp.shape(x)) for x in leaves)
        dtypes = tuple(str(jnp.result_type(x)) for x in leaves)
        key = (treedef, shapes, dtypes, block_rows)
        layout = _LAYOUT_CACHE.get(key)
        if layout is None:
            layout = _LAYOUT_CACHE[key] = cls(treedef, shapes, block_rows, dtypes)
        return layout

    # -- geometry -----------------------------------------------------------

    @property
    def n_leaves(self) -> int:
        return len(self.shapes)

    @property
    def n_rows(self) -> int:
        return sum(self.leaf_rows)

    @property
    def n_blocks(self) -> int:
        return self.n_rows // self.block_rows

    @property
    def leaf_slots(self) -> int:
        """Leaf-id axis of the per-leaf scratch accumulators, sublane-padded."""
        return -(-self.n_leaves // SUBLANE) * SUBLANE

    def block_leaf_ids(self) -> np.ndarray:
        """(n_blocks,) int32: which leaf each grid block belongs to (the
        kernels' scalar-prefetched SMEM map)."""
        return np.repeat(
            np.arange(self.n_leaves, dtype=np.int32),
            np.asarray(self.leaf_rows, np.int64) // self.block_rows,
        )

    def row_leaf_ids(self) -> np.ndarray:
        """(n_rows,) int32 leaf id per row (jnp segment reductions)."""
        return np.repeat(np.arange(self.n_leaves, dtype=np.int32), np.asarray(self.leaf_rows, np.int64))

    def leaf_inv_sizes(self) -> np.ndarray:
        """(leaf_slots, 1) f32: 1/size per leaf (pad slots hold 1.0)."""
        inv = np.ones((self.leaf_slots, 1), np.float32)
        inv[: self.n_leaves, 0] = 1.0 / np.maximum(np.asarray(self.sizes, np.float64), 1.0)
        return inv

    # -- pack / unpack ------------------------------------------------------

    def check_tree(self, tree: PyTree, what: str = "tree") -> list:
        """Flatten ``tree`` against this layout, failing LOUDLY on divergence
        (a moment tree drifting from the param treedef used to surface as an
        opaque flatten_up_to error deep inside the kernel dispatch)."""
        td = jax.tree_util.tree_structure(tree)
        if td != self.treedef:
            raise ValueError(
                f"{what} pytree structure does not match this ParamLayout.\n"
                f"  layout structure: {self.treedef}\n"
                f"  {what} structure:  {td}\n"
                "pack/unpack require the exact param treedef — did an optimizer "
                "moment tree diverge from the parameter tree?"
            )
        leaves = jax.tree_util.tree_leaves(tree)
        for i, (leaf, shape) in enumerate(zip(leaves, self.shapes)):
            if tuple(jnp.shape(leaf)) != shape:
                raise ValueError(
                    f"{what} leaf {i} has shape {tuple(jnp.shape(leaf))}, layout expects {shape}"
                )
        return leaves

    def pack(self, tree: PyTree, dtype=jnp.float32) -> jnp.ndarray:
        """Pytree -> (n_rows, LANE) buffer in ``dtype``, zero tail padding."""
        leaves = self.check_tree(tree, "pack input")
        dt = jnp.dtype(dtype)
        parts = []
        for leaf, size, rows in zip(leaves, self.sizes, self.leaf_rows):
            x = jnp.asarray(leaf).astype(dt).reshape(-1)
            parts.append(jnp.pad(x, (0, rows * LANE - size)))
        return jnp.concatenate(parts).reshape(self.n_rows, LANE)

    def unpack(self, buf: jnp.ndarray, dtype=None) -> PyTree:
        """(n_rows, LANE) buffer -> pytree of the layout's leaf shapes.

        Leaves keep the buffer dtype unless ``dtype`` overrides it.
        """
        flat = buf.reshape(-1)
        leaves = []
        for off, size, shape in zip(self.row_offsets, self.sizes, self.shapes):
            x = flat[off * LANE : off * LANE + size].reshape(shape)
            if dtype is not None:
                x = x.astype(dtype)
            leaves.append(x)
        return self.treedef.unflatten(leaves)

    def zeros(self, dtype=jnp.float32) -> jnp.ndarray:
        return jnp.zeros((self.n_rows, LANE), jnp.dtype(dtype))


@jax.tree_util.register_pytree_with_keys_class
class FlatBuffer:
    """A flat buffer + its layout, as a pytree node.

    tree_map descends to ``data``, so element-wise optimizer math written for
    pytrees runs unchanged on flat state; the layout rides in the treedef (so
    structure equality across jit/scan boundaries includes the geometry).
    """

    __slots__ = ("data", "layout")

    def __init__(self, data, layout: ParamLayout):
        self.data = data
        self.layout = layout

    def tree_flatten_with_keys(self):
        return ((jax.tree_util.GetAttrKey("data"), self.data),), self.layout

    @classmethod
    def tree_unflatten(cls, layout, children):
        return cls(children[0], layout)

    def unpack(self, dtype=None) -> PyTree:
        return self.layout.unpack(self.data, dtype)

    @property
    def shape(self):
        return jnp.shape(self.data)

    @property
    def dtype(self):
        return jnp.result_type(self.data)

    def __repr__(self):
        return f"FlatBuffer({self.shape}, {self.dtype}, leaves={self.layout.n_leaves})"


def is_flat(x: Any) -> bool:
    return isinstance(x, FlatBuffer)


def as_flat(tree: PyTree, layout: Optional[ParamLayout] = None, dtype=jnp.float32) -> FlatBuffer:
    """Normalize a pytree or FlatBuffer to a FlatBuffer (packing if needed)."""
    if is_flat(tree):
        return tree
    layout = layout or ParamLayout.for_tree(tree)
    return FlatBuffer(layout.pack(tree, dtype), layout)


def unpack_tree(tree: PyTree) -> PyTree:
    """Replace every FlatBuffer node in ``tree`` with its unpacked pytree
    (used at the checkpoint save boundary and by tests/diagnostics)."""
    return jax.tree_util.tree_map(
        lambda x: x.unpack() if is_flat(x) else x, tree, is_leaf=is_flat
    )
