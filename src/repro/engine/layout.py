"""Halo-resident field layout: fields stay put, halos move.

The WFA's two-orders-of-magnitude win comes from keeping every field
resident in PE-local memory for the whole run — only halo cells travel
(Rocki et al., arXiv:2010.03660).  The engine's analogue is this module:
instead of rebuilding a padded copy of every field per kernel launch
(``jnp.pad(mode="wrap")`` on one device, ``halo_pad``'s zero pad and slab
landing under ``shard_map``), each stenciled field is stored **once** at its run-wide
padded extent ``(nx + 2K, ny + 2K, nz)``, where ``K`` is the largest halo
window any scheduled segment needs (``max k·h`` over the plan, computed at
:func:`repro.engine.plan.plan` time).

Execution then touches memory three ways, none of which repacks a field:

* **enter/exit** — one conversion at each *program boundary* (start and end
  of one ``execute``), never inside the step loop;
* **margin refresh** — before a kernel launch reads a depth-``ph`` window,
  only the two X *slabs* are rewritten in place, since the kernel builds
  each loaded row's Y halo itself: on one device from the row's own
  interior (:func:`wrap_refresh_rows`), on a mesh from the two received Y
  slabs it takes as operands (:func:`repro.core.halo.halo_refresh_rows`,
  which exchanges all four by ``ppermute``);
* **double-buffered outputs** — a fused launch reads one resident buffer
  and writes each written field into a second buffer of the same extent
  (see :func:`repro.kernels.fused.build_fused_call`): the kernel's grid may
  then run its blocks in sequence over HBM, as Mosaic does, without a
  block's halo window reading rows an earlier block already stepped.  The
  step loops run launches in pairs
  (:func:`~repro.engine.executor.run_launches`), so each field's two
  buffers trade places with no copy, and the executors donate the entry
  buffers (``jax.jit(..., donate_argnums=...)``).

Margin contents are *transient*: they are refreshed to depth ``ph`` right
before each launch that reads them and are dead in between — a launch's
output leaves its margin rows undefined — so segments with different halo
depths share one resident buffer safely, and whatever inspects resident
state between launches (a finiteness probe, a snapshot) reads the interior
only.

Every operation here is **rank-agnostic over leading axes**: batched
ensemble plans (:class:`~repro.engine.options.RunOptions` with
``batch=B``) store each field as ``(B, nx + 2K, ny + 2K, nz)`` and one
refresh rewrites all B members' slabs in a single ``dynamic_update_slice``
— the (X, Y, Z) trailing axes are the only ones the layout ever touches.

>>> import numpy as np
>>> lay = HaloLayout(pad=2, shapes={"T": (4, 4, 3)})
>>> env = {"T": np.arange(48.0, dtype=np.float32).reshape(4, 4, 3)}
>>> padded = lay.enter(env)
>>> padded["T"].shape
(8, 8, 3)
>>> bool((lay.exit(padded)["T"] == env["T"]).all())
True
>>> batched = lay.enter({"T": np.stack([env["T"]] * 5)})
>>> batched["T"].shape
(5, 8, 8, 3)
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class HaloLayout:
    """Resident padded layout of one plan's fields.

    ``pad`` is the run-wide margin ``K`` (0 disables residency — enter and
    exit degrade to identity).  ``shapes`` records the *global* interior
    extents the plan was built from, as metadata for introspection only:
    enter/exit pad and slice whatever env they receive, which under
    ``shard_map`` is the per-device brick — and on a batched plan the
    ``(B, ...)``-leading stack — not these shapes.
    """

    pad: int
    shapes: Dict[str, Tuple[int, int, int]]

    def enter(self, env):
        """Pad every field to the resident extent (margins start zero; they
        are refreshed before any kernel reads them).  Leading (batch) axes
        pass through unpadded."""
        if self.pad == 0:
            return dict(env)
        K = self.pad

        def _pad(v):
            v = jnp.asarray(v)
            widths = ((0, 0),) * (v.ndim - 3) + ((K, K), (K, K), (0, 0))
            return jnp.pad(v, widths)

        return {n: _pad(v) for n, v in env.items()}

    def exit(self, env):
        """Slice every field's interior back out of the resident buffers."""
        if self.pad == 0:
            return dict(env)
        K = self.pad
        return {n: v[..., K:-K, K:-K, :] for n, v in env.items()}


def slab_rects(bx: int, by: int, h: int) -> Dict[str, Tuple[int, int, int, int]]:
    """Margin-slab geometry: name -> (ox, oy, sx, sy) in *brick* coordinates.

    The four depth-``h`` margin slabs of a (bx, by) brick, X slabs spanning
    the interior rows and Y slabs spanning the x-extended rows (so corners
    carry the diagonal neighbour / double-wrap data).  The rectangles are
    pairwise disjoint and exactly cover the margin frame.  Shared by the
    wrap refresh, the mesh exchange (:func:`repro.core.halo.exchange_slabs`)
    and the overlap scheduler's strip assembly, so the three cannot drift.
    """
    return {
        "lo_x": (-h, 0, h, by),
        "hi_x": (bx, 0, h, by),
        "lo_y": (-h, -h, bx + 2 * h, h),
        "hi_y": (-h, by, bx + 2 * h, h),
    }


def wrap_slabs(resident, margin: int, h: int) -> Dict[str, jnp.ndarray]:
    """Extract the depth-``h`` wrap margin slabs into *separate* buffers.

    The double-buffered half of the single-device margin refresh: the slab
    values are exactly what ``jnp.pad(interior, h, mode="wrap")`` would put
    in the margin frame (Y slabs assembled from the X slabs + interior edge
    columns, so corners wrap in both axes bitwise), but they live in their
    own small arrays — never aliasing the resident buffer an in-flight
    interior kernel writes — until :func:`land_slabs` stores them.
    """
    K = margin
    bx = resident.shape[-3] - 2 * K
    by = resident.shape[-2] - 2 * K
    lo_x = resident[..., K + bx - h : K + bx, K : K + by, :]
    hi_x = resident[..., K : K + h, K : K + by, :]
    lo_y = jnp.concatenate(
        [
            lo_x[..., :, by - h : by, :],
            resident[..., K : K + bx, K + by - h : K + by, :],
            hi_x[..., :, by - h : by, :],
        ],
        axis=-3,
    )
    hi_y = jnp.concatenate(
        [
            lo_x[..., :, 0:h, :],
            resident[..., K : K + bx, K : K + h, :],
            hi_x[..., :, 0:h, :],
        ],
        axis=-3,
    )
    return {"lo_x": lo_x, "hi_x": hi_x, "lo_y": lo_y, "hi_y": hi_y}


def land_slabs(resident, slabs: Dict[str, jnp.ndarray], margin: int, h: int):
    """Store extracted margin slabs into the resident buffer's margin frame.

    The landing half of the refresh: one ``dynamic_update_slice`` write per
    given slab at its :func:`slab_rects` rectangle (disjoint, so order is
    irrelevant).  Leading (batch) axes pass through whole.
    """
    if h == 0:
        return resident
    K = margin
    bx = resident.shape[-3] - 2 * K
    by = resident.shape[-2] - 2 * K
    rects = slab_rects(bx, by, h)
    lead = (0,) * (resident.ndim - 3)
    for name, slab in slabs.items():
        ox, oy, _, _ = rects[name]
        resident = jax.lax.dynamic_update_slice(
            resident, slab, lead + (K + ox, K + oy, 0)
        )
    return resident


def wrap_refresh(resident, margin: int, h: int):
    """Refresh the depth-``h`` wrap margin of a resident array in place.

    The single-device analogue of :func:`repro.core.halo.halo_pad`'s landing:
    reproduces exactly what ``jnp.pad(interior, h, mode="wrap")`` would have
    built — the periodic margins the roll interpreter's semantics demand —
    but as four ``dynamic_update_slice`` edge slabs into the standing buffer
    (:func:`wrap_slabs` extracted, :func:`land_slabs` stored) instead of a
    fresh padded copy of the whole field.

    ``resident`` may carry leading (batch) axes: slabs span them whole, so
    one update refreshes every ensemble member's margin at once.
    """
    if h == 0:
        return resident
    return land_slabs(resident, wrap_slabs(resident, margin, h), margin, h)


def wrap_refresh_rows(resident, margin: int, h: int):
    """Refresh only the depth-``h`` X margin rows (``lo_x``/``hi_x``).

    The single-device step's refresh: its kernel builds each loaded row's Y
    halo from the row's own interior (see
    :func:`repro.kernels.fused.build_fused_call`), so only the rows above
    and below the brick have to be written — two runs of whole rows, where
    the Y slabs would cost a narrow write into every row.
    """
    if h == 0:
        return resident
    slabs = wrap_slabs(resident, margin, h)
    rows = {name: slabs[name] for name in ("lo_x", "hi_x")}
    return land_slabs(resident, rows, margin, h)


def strip_window(
    resident,
    slabs: Dict[str, jnp.ndarray],
    margin: int,
    h: int,
    region,
    bx: int,
    by: int,
):
    """Assemble one boundary region's padded input window.

    ``region`` is a shell :class:`repro.compiler.ir.RegionSpec`; the window
    is the ``(rx + 2h, ry + 2h, Z)`` input its depth-``h`` (= ``k·halo``)
    kernel launch consumes — brick cells sliced from the **pre-step**
    resident buffer, margin cells overwritten from the landed ``slabs``
    (rect intersection with :func:`slab_rects`).  Cell for cell this equals
    the window the monolithic kernel would have read off a refreshed
    buffer, which is what makes the split bitwise-exact; slicing from the
    pre-step buffer is also what lets the interior kernel write the same
    buffer in place concurrently.
    """
    K = margin
    wx0, wy0 = region.x0 - h, region.y0 - h
    wx1, wy1 = region.x0 + region.rx + h, region.y0 + region.ry + h
    win = resident[..., K + wx0 : K + wx1, K + wy0 : K + wy1, :]
    lead = (0,) * (resident.ndim - 3)
    for name, (ox, oy, sx, sy) in slab_rects(bx, by, h).items():
        ix0, iy0 = max(ox, wx0), max(oy, wy0)
        ix1, iy1 = min(ox + sx, wx1), min(oy + sy, wy1)
        if ix0 >= ix1 or iy0 >= iy1:
            continue
        piece = slabs[name][..., ix0 - ox : ix1 - ox, iy0 - oy : iy1 - oy, :]
        win = jax.lax.dynamic_update_slice(
            win, piece, lead + (ix0 - wx0, iy0 - wy0, 0)
        )
    return win


def land_region(resident, out, margin: int, region):
    """Store one region's kernel output into the resident buffer interior."""
    lead = (0,) * (resident.ndim - 3)
    return jax.lax.dynamic_update_slice(
        resident, out, lead + (margin + region.x0, margin + region.y0, 0)
    )
