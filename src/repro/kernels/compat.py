"""Element-offset block specs for overlapping stencil windows.

The index map of such a spec returns cell offsets, not block indices, so
neighbouring grid blocks may read overlapping (halo) rows.  Pallas takes
element indexing on every dimension of a block or on none, so the helper
wraps them all.
"""
from __future__ import annotations

from typing import Callable, Optional, Sequence, Tuple

from jax.experimental import pallas as pl


def element_block_spec(block_shape: Sequence[int], index_map: Callable,
                       padding: Optional[Sequence[Tuple[int, int]]] = None):
    """BlockSpec with element-offset indexing + optional (lo, hi) zero pads."""
    if padding is None:
        padding = [(0, 0)] * len(block_shape)
    dims = tuple(
        pl.Element(n, padding=tuple(p)) if tuple(p) != (0, 0) else pl.Element(n)
        for n, p in zip(block_shape, padding))
    return pl.BlockSpec(dims, index_map)
