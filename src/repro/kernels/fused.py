"""Generic fused stencil kernel — arbitrary tap sets, outputs, time tiles.

This generalizes the hand-fused 7-point :mod:`repro.kernels.stencil7` to any
canonical tap form produced by :mod:`repro.compiler.ir`: arbitrary (dz, dx,
dy) offsets within a halo of depth ``h`` (off-axis/diagonal taps included),
variable-coefficient products of up to two taps, several ``UpdateOp``s — and
several *output fields* — fused into a single ``pl.pallas_call`` per loop
body.  Sequential updates inside one body see earlier updates' *local*
values (dx = dy = 0 reads only — the lowering pass rejects the rest),
mirroring the Control Tile's ordered RPC stream.

Layout: fields are (X, Y, Z) with Z on the 128 lanes and Y on the 8
sublanes of a vreg.  Mosaic takes a block's Y extent only as a multiple of 8
or as the array's whole extent, so the grid blocks X alone: each grid step
loads, per input field, an overlapping ``(bxb + 2kh, Y, Z)`` window that
spans the whole padded Y extent (element indexing on X), and ``bxb`` is the
largest X block whose double-buffered windows and body temporaries fit the
kernel's VMEM (:func:`pick_x_block`).

Time tiling (``time_tile=k``): each window is stepped ``k`` times in VMEM,
the valid region shrinking by ``h`` per sub-step (trapezoid blocking), so
the caller pays the halo exchange / wrap pad once per *tile* instead of
once per step.  The
Dirichlet Moat mask is applied per sub-step from global coordinates — with
``wrap=True`` (single device, ``jnp.pad(mode="wrap")`` margins) coordinates
are taken modulo the grid so halo cells evolve exactly like the domain cells
they mirror, keeping the tiled run bit-identical to k untiled steps.

The caller supplies halo-padded inputs: ``jnp.pad(..., mode="wrap")`` on a
single device (matching the interpreter's ``jnp.roll`` semantics exactly) or
``core.halo.halo_pad`` (ICI ppermute) inside ``shard_map`` — depth ``k·h``
either way.  ``coords`` is a (1, 2) int32 array with the brick's global cell
origin so one kernel image serves every brick — how one Worker image serves
the whole WSE fabric.

Halo-resident mode (``margin=``, the engine's
:class:`~repro.engine.layout.HaloLayout`) takes inputs at the run-wide
padded extent, their margins refreshed in place by the caller, and
double-buffers: each launch reads one resident buffer and writes its
written fields into another of the same extent, so the per-launch pad goes
and the grid may run its blocks in sequence over HBM without reading a row
it already stepped.  Only the overlap split's region launches write in
place, and only in interpret mode.

Reverse-mode AD never differentiates through this kernel: differentiable
plans (``RunOptions(differentiable=True)``) keep donation and the resident
layout off, and ``engine.differentiable_runner`` wraps each launch
in a ``custom_vjp`` whose backward replays the roll-interpreter reference —
exact for the affine bodies the lowering pass admits, and indifferent to
input aliasing because the primal kernel is only ever called on
non-donated, margin-free arrays under AD.
"""
from __future__ import annotations

import functools
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compiler.ir import LoweringError
from repro.kernels.compat import element_block_spec


#: scoped VMEM one fused launch may use.  A v5e core holds 128 MiB of VMEM
#: and Mosaic scopes 16 MiB by default; the kernel sets its own limit to
#: this and sizes its X block (and the planner its time tile) to fit it.
#: Larger blocks would recompute less halo but compile slower: Mosaic
#: unrolls the body over every vreg of every sub-step.
VMEM_LIMIT = 32 * 2**20
#: largest X block a launch takes (rows of full-Y windows)
_X_BLOCK_MAX = 32
#: body temporaries per field, in windows: the loaded window, its z-rolled
#: taps and the accumulators (the v5e compiler's scoped-VMEM need for the
#: 7-point heat body at 512x512x128 puts them at 1.0 window at k=1 and 2.6
#: at k=4)
_TEMPS = 3


def _tile_bytes(rows: int, ys: int, nz: int, itemsize: int) -> int:
    """Bytes of a (rows, ys, nz) VMEM value: Y pads to 8 sublanes, Z to
    whole 128-lane vregs."""
    return rows * (-(-ys // 8) * 8) * (-(-nz // 128) * 128) * itemsize


def window_vmem_bytes(field_specs: Dict[str, Tuple[int, object]],
                      written: Sequence[str], halo: int, k: int, bxb: int,
                      ya: int, ry: int) -> int:
    """Upper bound on the VMEM one grid step needs, from shapes alone.

    Every input window ``(bxb + 2kh, ya, nz)`` and output block ``(bxb, ya,
    nz)`` is double-buffered by the Pallas pipeline, and the body keeps up
    to :data:`_TEMPS` more region-sized windows live per field.
    """
    rows = bxb + 2 * k * halo
    total = 0
    for name, (nz, dtype) in field_specs.items():
        isz = jnp.dtype(dtype).itemsize
        total += 2 * _tile_bytes(rows, ya, nz, isz)
        total += _TEMPS * _tile_bytes(rows, ry + 2 * k * halo, nz, isz)
        if name in written:
            total += 2 * _tile_bytes(bxb, ya, nz, isz)
    return total


def pick_x_block(field_specs, written, halo: int, k: int, rx: int, ya: int,
                 ry: int) -> int:
    """Largest divisor of ``rx`` (≤ ``_X_BLOCK_MAX``) whose grid step fits
    :data:`VMEM_LIMIT`, or 0 when not even one row does."""
    for b in range(min(rx, _X_BLOCK_MAX), 0, -1):
        if rx % b == 0 and window_vmem_bytes(field_specs, written, halo, k, b,
                                             ya, ry) <= VMEM_LIMIT:
            return b
    return 0


def _roll_z(a, dz):
    """``a`` shifted so lane ``z`` holds ``a[..., z + dz]`` (wrapping)."""
    if dz == 0:
        return a
    return pltpu.roll(a, (-dz) % a.shape[-1], a.ndim - 1)


def _apply_updates(updates, cur, h, out_x, out_y, gx0, gy0, nx, ny, wrap,
                   interpret):
    """One sub-step: apply every update over the (out_x, out_y) region.

    ``cur`` holds full-Z arrays of extent (out_x + 2h, out_y + 2h); returns
    the post-step dict shrunk to (out_x, out_y).  ``gx0, gy0`` are the global
    coordinates of the *output* region's origin; with ``wrap`` they are taken
    modulo the grid so wrap-pad margin cells mask like the cells they mirror.

    Z (lanes) is handled two ways with the same per-cell arithmetic.  On
    Mosaic every value spans the whole Z extent: a z-shifted tap is a lane
    rotation of its field and an update's target window ``[z0, z0+zlen)``
    is a lane mask on the final select (Mosaic has no lowering for a
    dynamic-update-slice splice).  Under the interpreter the taps read the
    window itself and the result is spliced back in place: the splice keeps
    XLA from fusing the update into whatever consumes the kernel's output,
    which would change FMA contraction and make a result depend on the
    program around the kernel.
    """
    row = jax.lax.broadcasted_iota(jnp.int32, (out_x, out_y, 1), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (out_x, out_y, 1), 1)
    gx = gx0 + row
    gy = gy0 + col
    if wrap:
        gx = gx % nx
        gy = gy % ny
    interior = (gx > 0) & (gx < nx - 1) & (gy > 0) & (gy < ny - 1)

    center: Dict[str, jnp.ndarray] = {}   # full-Z out-sized blocks, updated
    rolled: Dict[tuple, jnp.ndarray] = {}  # Mosaic: lane-rotated sources

    def read(tap, z):
        # a field already updated this sub-step is read block-locally
        # (lowering guarantees dx == dy == 0 there); others through the halo
        local = tap.field in center
        src = center[tap.field] if local else cur[tap.field]
        if z is not None:
            src = src[:, :, z.start + tap.dz:z.stop + tap.dz]
        else:
            key = (tap.field, tap.dz, local)
            if key not in rolled:
                rolled[key] = _roll_z(src, tap.dz)
            src = rolled[key]
        if local:
            return src
        return src[h + tap.dx:h + tap.dx + out_x, h + tap.dy:h + tap.dy + out_y]

    for u in updates:
        if u.field in center:
            old = center[u.field]
        else:
            old = cur[u.field][h:h + out_x, h:h + out_y, :]
        nz = old.shape[-1]
        dtype = old.dtype
        whole = (u.z0, u.zlen) == (0, nz)
        z = None if whole or not interpret else slice(u.z0, u.z0 + u.zlen)
        # group products sharing a scalar coefficient: sum first, multiply
        # once — fewer VPU multiplies and the same association the source
        # spelling `c * (T_E + T_W + ...)` used, so rounding matches the
        # interpreter to ~1 ulp.
        groups: Dict[float, jnp.ndarray] = {}
        for coeff, taps in u.terms:
            t = read(taps[0], z)
            for tap in taps[1:]:
                t = t * read(tap, z)
            groups[coeff] = t if coeff not in groups else groups[coeff] + t
        acc = None
        for coeff, t in groups.items():
            if coeff != 1.0:
                t = dtype.type(coeff) * t
            acc = t if acc is None else acc + t
        if acc is None:
            acc = jnp.full((out_x, out_y, nz if z is None else u.zlen),
                           u.const, dtype)
        elif u.const != 0.0:
            acc = acc + dtype.type(u.const)

        if whole:
            center[u.field] = jnp.where(interior, acc, old)
        elif z is not None:
            center[u.field] = jax.lax.dynamic_update_slice(
                old, jnp.where(interior, acc, old[:, :, z]), (0, 0, u.z0))
        else:
            lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, nz), 2)
            keep = interior & (lane >= u.z0) & (lane < u.z0 + u.zlen)
            center[u.field] = jnp.where(keep, acc, old)
        # later reads of this field see the new values
        rolled = {key: v for key, v in rolled.items() if key[0] != u.field}

    out = {}
    for name, a in cur.items():
        out[name] = (center[name] if name in center
                     else a[h:h + out_x, h:h + out_y, :])
    return out


def _fused_body(updates, in_names, written, h, k, wrap, bxb, ry, y_lo, nx,
                ny, margin, yhalo, interpret, coords_ref, *refs):
    kh = k * h
    n_in = len(in_names)
    in_refs = dict(zip(in_names, refs[:n_in]))
    n_slabs = 2 * n_in if yhalo == "slabs" else 0
    slabs = refs[n_in:n_in + n_slabs]   # lo_y, hi_y of each input field
    out_refs = dict(zip(written, refs[n_in + n_slabs:]))
    # the loaded windows span the array's whole Y extent; the body steps
    # the ry output rows of its region (plus their depth-kh halo)
    if yhalo == "wrap":
        # each row's Y halo is the wrap of its own interior, built here so
        # the caller refreshes only the X margin rows
        cur = {n: jnp.concatenate([r[:, margin + ry - kh:margin + ry, :],
                                   r[:, margin:margin + ry, :],
                                   r[:, margin:margin + kh, :]], axis=1)
               for n, r in in_refs.items()}
    elif yhalo == "slabs":
        # each row's Y halo comes from the neighbours' received Y slabs,
        # operands of the launch, so the caller lands only the X slabs
        cur = {n: jnp.concatenate([slabs[2 * j][...],
                                   r[:, margin:margin + ry, :],
                                   slabs[2 * j + 1][...]], axis=1)
               for j, (n, r) in enumerate(in_refs.items())}
    else:
        cur = {n: r[:, y_lo:y_lo + ry + 2 * kh, :]
               for n, r in in_refs.items()}
    i = pl.program_id(0)
    # global origin of the loaded window (halo depth k·h below the block)
    gx0 = coords_ref[0, 0] + i * bxb - kh
    gy0 = coords_ref[0, 1] - kh
    for s in range(k):
        out_x = bxb + 2 * (k - s - 1) * h
        out_y = ry + 2 * (k - s - 1) * h
        gx0 = gx0 + h   # origin of this sub-step's output region
        gy0 = gy0 + h
        cur = _apply_updates(updates, cur, h, out_x, out_y, gx0, gy0,
                             nx, ny, wrap, interpret)
    for name in written:
        if margin:
            # the output block is a whole resident row: cells outside the
            # region keep their pre-launch values
            out_refs[name][...] = in_refs[name][kh:kh + bxb, :, :]
            out_refs[name][:, y_lo + kh:y_lo + kh + ry, :] = cur[name]
        else:
            out_refs[name][...] = cur[name]


def build_fused_call(updates: Sequence, field_specs: Dict[str, Tuple[int, object]],
                     halo: int, bx: int, by: int, nx: int, ny: int,
                     interpret: bool = False,
                     time_tile: int = 1, wrap: bool = False,
                     margin: int = 0, region=None):
    """Build the fused kernel for one loop body.

    ``updates``     — :class:`repro.compiler.ir.AffineUpdate`s, program order.
    ``field_specs`` — ordered ``name -> (nz, dtype)`` for every field the body
                      reads or writes; all share the brick extent (bx, by).
    ``bx, by``      — brick extent (global grid on 1 device, local brick under
                      ``shard_map``); ``nx, ny`` — global extent for the Moat.
    ``time_tile``   — sub-steps fused per launch (k); inputs carry ``k·halo``
                      margins.  ``wrap`` marks wrap-pad margins (single
                      device) so the per-sub-step Moat mask wraps coordinates.
    ``margin``      — halo-resident mode: inputs arrive at the *run-wide*
                      padded extent (bx + 2·margin, by + 2·margin, nz) with
                      ``margin >= k·halo`` (the engine's
                      :class:`~repro.engine.layout.HaloLayout`), the kernel
                      reads its depth-``k·halo`` window from inside that
                      margin, and every written field is emitted at the
                      resident extent into a **separate** buffer (double
                      buffering: no operand the launch reads is aliased, so
                      the grid may run its blocks in sequence over HBM).
                      The output's brick rows are written; its margin rows
                      are left undefined — margins are transient and the
                      caller refreshes them before any read.  A monolithic
                      launch reads no Y margin: with ``wrap`` (one device)
                      it builds each loaded row's Y halo from the row's own
                      interior (:func:`repro.engine.layout.wrap_refresh_rows`),
                      and on a mesh (``halo > 0``) from the neighbours'
                      ``lo_y`` / ``hi_y`` slabs, ``(bx + 2·k·halo, k·halo,
                      nz)`` operands that follow the inputs, two per field
                      (:func:`repro.core.halo.halo_refresh_rows`), so the
                      caller lands only the X margin rows.
    ``region``      — a :class:`repro.compiler.ir.RegionSpec` *windowing*
                      the launch (resident mode only): the grid covers the
                      region's (rx, ry) output cells instead of the whole
                      brick, windows and output blocks offset by the region
                      origin.  The overlap scheduler uses this for the
                      interior launch — the region sits ``k·halo`` inside
                      the brick edge, so its input windows never touch the
                      margin frame and the launch needs no refreshed halo
                      data.  The caller must offset ``coords`` by the
                      region origin so the Moat mask stays global.  A region
                      launch writes **in place**: each written field aliases
                      its input buffer via ``input_output_aliases``, which
                      is safe only where blocks are evaluated functionally
                      (interpret mode; the planner keeps the split off on
                      Mosaic).

    Returns ``call(coords, *padded) -> tuple(new_fields)`` where ``padded``
    are the (bx + 2·k·halo, by + 2·k·halo, nz) inputs (resident extent when
    ``margin`` is set) in ``field_specs`` order, followed on a monolithic
    mesh launch at the resident extent by each field's ``lo_y`` and
    ``hi_y`` slabs, and the outputs are the
    written fields, in first-written order — full (bx, by, nz) arrays, or
    resident-extent buffers when ``margin`` is set.
    """
    in_names = list(field_specs)
    written = []
    for u in updates:
        if u.field not in written:
            written.append(u.field)
    nz_of = {n: s[0] for n, s in field_specs.items()}
    h = halo
    k = time_tile
    if margin and margin < k * h:
        raise ValueError(f"resident margin {margin} < window halo {k * h}")
    if region is not None and not margin:
        raise ValueError("region windowing requires resident margin mode")
    # region mode: the grid tiles the region's output cells; windows and
    # output blocks shift by the region origin inside the resident buffer
    rx, ry = (bx, by) if region is None else (region.rx, region.ry)
    ox, oy = (0, 0) if region is None else (region.x0, region.y0)
    kh = k * h
    # Mosaic takes a block's second-minor (Y, sublane) extent only as a
    # multiple of 8 or as the whole array extent; a depth-kh halo window
    # is neither, so every window and output block spans the array's
    # whole Y extent and the grid blocks X (the untiled leading axis) only
    ya = by + 2 * (margin if margin else kh)
    bxb = pick_x_block(field_specs, written, h, k, rx, ya, ry)
    if not bxb:
        raise LoweringError(
            f"fused window ({1 + 2 * kh}, {ya}, nz) at k={k} does not fit "
            f"{VMEM_LIMIT >> 20} MiB of VMEM")
    if interpret:
        # the interpreter runs the grid as an XLA loop; blocks of at most 8
        # rows keep that loop, and with it the kernel's arithmetic, apart
        # from the caller's fusions — a one-step grid would let XLA fuse the
        # kernel into its consumer and change FMA contraction, so a result
        # would depend on the program around the kernel
        bxb = max(b for b in range(1, min(bxb, 8) + 1) if rx % b == 0)
    grid = (rx // bxb,)
    # Y offset of the region's depth-kh window inside the loaded window
    y_lo = margin - kh + oy if margin else 0

    # a monolithic launch on the resident layout reads no Y margin: it
    # builds each loaded row's Y halo from the row itself (wrap) or from
    # the received Y slabs (mesh; see _fused_body)
    yhalo = None
    if margin and kh and region is None:
        yhalo = "wrap" if wrap else "slabs"
    body = functools.partial(_fused_body, tuple(updates), tuple(in_names),
                             tuple(written), h, k, wrap, bxb, ry, y_lo,
                             nx, ny, margin, yhalo, interpret)
    # window origin inside the input: the kernel always consumes a
    # (bxb + 2kh)-row window; with a resident margin that window sits
    # `margin - kh` rows inside the buffer edge (legacy inputs arrive
    # already window-aligned — their whole extent IS the padded window).
    off_x = margin - kh + ox if margin else 0
    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)]
    for name in in_names:
        in_specs.append(element_block_spec(
            (bxb + 2 * kh, ya, nz_of[name]),
            lambda i, ax=off_x: (ax + i * bxb, 0, 0)))
    if yhalo == "slabs":
        # the (bx + 2kh, kh, nz) lo_y / hi_y slabs span the x-extended rows,
        # so a block's window starts at the same row in slab coordinates
        for name in in_names:
            in_specs += [element_block_spec(
                (bxb + 2 * kh, kh, nz_of[name]),
                lambda i: (i * bxb, 0, 0))] * 2
    if margin:
        # outputs at the resident extent; the grid writes only the region's
        # rows, and the body copies the pre-launch values of every other
        # cell of those rows.  A monolithic launch writes a fresh buffer:
        # Mosaic runs the grid in sequence over HBM, so writing in place
        # would let block i+1's halo window read rows block i already
        # stepped.  Its margin rows are left unwritten (margins are
        # transient).  A region launch aliases each written field's input
        # buffer, so the rest of the brick keeps its values.
        out_specs = [element_block_spec(
            (bxb, ya, nz_of[n]),
            lambda i: (margin + ox + i * bxb, 0, 0)) for n in written]
        out_shape = [jax.ShapeDtypeStruct(
            (bx + 2 * margin, by + 2 * margin, nz_of[n]), field_specs[n][1])
            for n in written]
        aliases = ({} if region is None else
                   {1 + in_names.index(n): o for o, n in enumerate(written)})
    else:
        out_specs = [pl.BlockSpec((bxb, by, nz_of[n]), lambda i: (i, 0, 0))
                     for n in written]
        out_shape = [jax.ShapeDtypeStruct((bx, by, nz_of[n]), field_specs[n][1])
                     for n in written]
        aliases = {}

    call = pl.pallas_call(
        body,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
        name="wfa_stencil",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=VMEM_LIMIT),
    )

    def fused(coords, *padded):
        with jax.named_scope("wfa.kernel.stencil"):
            out = call(coords, *padded)
        return tuple(out) if isinstance(out, (list, tuple)) else (out,)

    return fused, tuple(written)
