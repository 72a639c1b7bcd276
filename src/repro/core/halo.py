"""Distributed bricks + halo exchange — the WSE fabric on a TPU mesh.

The paper's 1×1×Z decomposition gives every tile a Z-column and exchanges
X/Y neighbour planes over single-cycle fabric hops.  The TPU analogue bricks
the (X, Y) plane over the (``data``, ``model``) mesh axes — each chip owns a
(bx, by, Z) brick — and exchanges depth-``h`` ghost zones with
``lax.ppermute`` along each axis: a nearest-neighbour ICI transfer, the
direct analogue of the WSE's W→C→E / N→C→S background threads.  Time-tiled
segments exchange depth ``k·h`` once per k steps (temporal blocking — the
engine's communication amortization).

This module owns the mesh-level primitives (``halo_pad``,
``halo_refresh_rows``, the traced Moat mask, the sharded roll-interpreter
step); scheduling and backend dispatch live in :mod:`repro.engine`.
``run_sharded`` is the thin mesh entry point into that engine, so the
paper's Fig. 3 script runs unchanged on 1 device or 512.
"""
from __future__ import annotations

import threading
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import stencil as st
from repro.core.jaxcompat import make_mesh
from repro.core.program import Program

#: device scope of the neighbour transfers (and the slabs they send)
EXCHANGE = "wfa.halo.exchange"
#: device scope of :func:`halo_pad`'s whole-brick pad, the repack
PAD = "wfa.halo.pad"

#: per thread, one running total per open :func:`counted` trace
_sent = threading.local()


def counted(step, members: int = 1):
    """``step``, which notes each time it is traced the bytes one chip sends
    by ``ppermute`` in one application, as ``halo_bytes`` (the mean over the
    chips: an edge brick sends nothing outward, so on a 2x2 mesh every chip
    sends one X slab and one Y slab of each field).  ``members`` is the
    ensemble width a ``vmap`` inside ``step`` hides from the traced shapes.
    """

    def run(env):
        totals = _sent.__dict__.setdefault("totals", [])
        totals.append(0.0)
        try:
            return step(env)
        finally:
            run.halo_bytes = totals.pop() * members

    run.halo_bytes = 0.0
    return run


def _ppermute_shift(x, axis_name: str, n: int, direction: int):
    """Receive neighbour data from ``direction`` (+1: from lower index)."""
    totals = getattr(_sent, "totals", None)
    if totals:
        # n - 1 of the axis' n chips send
        totals[-1] += x.size * x.dtype.itemsize * (n - 1) / n
    if direction > 0:
        perm = [(i, i + 1) for i in range(n - 1)]
    else:
        perm = [(i + 1, i) for i in range(n - 1)]
    return jax.lax.ppermute(x, axis_name, perm)


def halo_pad(local, h: int, ax_x: str, ax_y: str, mx: int, my: int):
    """Pad a (bx, by, Z) brick with depth-``h`` halos in X and Y.

    Edge bricks receive zeros in the out-of-domain halo; those cells are
    never read by interior updates because domain-boundary cells are stored
    *inside* the edge bricks (the Moat), matching the paper's layout.
    Leading (batch) axes pass through: a ``(B, bx, by, Z)`` ensemble brick
    moves all B members' halo planes in the same ``ppermute``.

    One zero pad of the whole brick (the repack, scope ``wfa.halo.pad``),
    then the four margin slabs exchanged (:func:`exchange_slabs`) and
    landed in it, so the kernel's window is bitwise the one the
    halo-resident path builds (:func:`halo_refresh_rows`).  Not two
    concatenates: XLA rewrites a concatenate inside the step loop into
    in-place writes to a fresh buffer, one of them a second copy of the
    whole brick that carries no scope.
    """
    if h == 0:
        return local
    from repro.engine.layout import land_slabs

    widths = ((0, 0),) * (local.ndim - 3) + ((h, h), (h, h), (0, 0))
    with jax.named_scope(PAD):
        padded = jnp.pad(local, widths)
    slabs = exchange_slabs(padded, h, h, ax_x, ax_y, mx, my)
    with jax.named_scope("wfa.engine.margin_refresh"):
        return land_slabs(padded, slabs, h, h)


def exchange_slabs(resident, margin: int, h: int, ax_x: str, ax_y: str,
                   mx: int, my: int):
    """Exchange the depth-``h`` margin slabs into *separate* buffers.

    The mesh counterpart of :func:`repro.engine.layout.wrap_slabs`: two
    ``ppermute`` edge transfers per axis, the Y transfers sourced from the
    x-extended rows (own edge columns flanked by the incoming X slabs'
    corner pieces), so corner cells arrive from the diagonal neighbour in
    two fabric hops, zero fill on domain-edge bricks included.  The slabs stay in their own
    small arrays until :func:`repro.engine.layout.land_slabs` stores them:
    the returned dict is the *in-flight exchange* the overlap scheduler
    launches the interior kernel alongside, never aliasing the resident
    buffer that kernel writes.  Leading (batch) axes travel whole.
    """
    K = margin
    bx = resident.shape[-3] - 2 * K
    by = resident.shape[-2] - 2 * K
    with jax.named_scope(EXCHANGE):
        # X axis: slabs of the interior's edge rows (full interior Y extent).
        lo_x = _ppermute_shift(resident[..., K + bx - h:K + bx, K:K + by, :],
                               ax_x, mx, +1)
        hi_x = _ppermute_shift(resident[..., K:K + h, K:K + by, :], ax_x, mx, -1)
        # Y axis: sources span the x-extended rows (corner pieces from the X
        # slabs just received).
        src_lo = jnp.concatenate([
            lo_x[..., :, by - h:by, :],
            resident[..., K:K + bx, K + by - h:K + by, :],
            hi_x[..., :, by - h:by, :],
        ], axis=-3)
        src_hi = jnp.concatenate([
            lo_x[..., :, 0:h, :],
            resident[..., K:K + bx, K:K + h, :],
            hi_x[..., :, 0:h, :],
        ], axis=-3)
        lo_y = _ppermute_shift(src_lo, ax_y, my, +1)
        hi_y = _ppermute_shift(src_hi, ax_y, my, -1)
        return {"lo_x": lo_x, "hi_x": hi_x, "lo_y": lo_y, "hi_y": hi_y}


def halo_refresh_rows(resident, margin: int, h: int, ax_x: str, ax_y: str,
                      mx: int, my: int):
    """Exchange the four depth-``h`` margin slabs, land only the two X slabs.

    The mesh step's refresh: its monolithic kernel takes the received
    ``lo_y`` / ``hi_y`` slabs as operands and builds each loaded row's Y
    halo from them (see :func:`repro.kernels.fused.build_fused_call`), so
    only the rows above and below the brick are written in place — two
    runs of whole rows, where the Y slabs would cost a narrow write into
    every row.  Returns ``(resident, lo_y, hi_y)``: the same ``ppermute``
    traffic as :func:`halo_pad`, and the kernel sees the same window.
    Leading (batch) axes pass through — one slab transfer refreshes every
    ensemble member.
    """
    from repro.engine.layout import land_slabs

    slabs = exchange_slabs(resident, margin, h, ax_x, ax_y, mx, my)
    rows = {name: slabs[name] for name in ("lo_x", "hi_x")}
    with jax.named_scope("wfa.engine.margin_refresh"):
        resident = land_slabs(resident, rows, margin, h)
    return resident, slabs["lo_y"], slabs["hi_y"]


def local_moat_mask(bx: int, by: int, ax_x: str, ax_y: str, mx: int, my: int):
    """(bx, by, 1) mask, False on global-domain-edge cells of this brick.

    Traced from ``axis_index`` so the same SPMD program serves all bricks —
    exactly how one Worker kernel image serves the whole WSE fabric.
    """
    cx = jax.lax.axis_index(ax_x)
    cy = jax.lax.axis_index(ax_y)
    gx = cx * bx + jax.lax.broadcasted_iota(jnp.int32, (bx, by, 1), 0)
    gy = cy * by + jax.lax.broadcasted_iota(jnp.int32, (bx, by, 1), 1)
    nx, ny = mx * bx, my * by
    return (gx > 0) & (gx < nx - 1) & (gy > 0) & (gy < ny - 1)


def evaluate_padded(expr: st.StencilExpr, env_padded: Dict[str, jnp.ndarray],
                    target_z: slice, h: int, bx: int, by: int):
    """Evaluate a stencil expression on depth-``h`` halo-padded bricks."""
    if isinstance(expr, st.Const):
        return expr.value
    if isinstance(expr, st.Term):
        a = env_padded[expr.field_name]
        x0 = h + expr.dx
        y0 = h + expr.dy
        return a[x0:x0 + bx, y0:y0 + by, expr.zslice_obj()]
    if isinstance(expr, st.BinOp):
        lhs = evaluate_padded(expr.lhs, env_padded, target_z, h, bx, by)
        rhs = evaluate_padded(expr.rhs, env_padded, target_z, h, bx, by)
        return st._BINOPS[expr.op](lhs, rhs)
    raise TypeError(type(expr))


def interp_step_sharded(ops, ax_x: str, ax_y: str, mx: int, my: int):
    """Roll-interpreter step for one op group on halo-padded bricks.

    The ``shard_map``-local analogue of ``program._interp_step``: one halo
    exchange + padded evaluation per op, Moat mask from mesh coordinates.
    The engine hands this out (via ``compile_body``) as the ``jit`` backend
    and the sharded interpreter fallback, so the two cannot diverge.
    """

    def step(e):
        e = dict(e)
        masks = {}  # (bx, by) -> traced Moat mask, built once per step
        for op in ops:
            h = max(1, op.expr.max_offset())
            names = {t.field_name for t in op.expr.terms()}
            padded = {n: halo_pad(e[n], h, ax_x, ax_y, mx, my) for n in names}
            f = e[op.field_name]
            bx, by, _ = f.shape
            val = evaluate_padded(op.expr, padded, op.target_z, h, bx, by)
            if (bx, by) not in masks:
                masks[bx, by] = local_moat_mask(bx, by, ax_x, ax_y, mx, my)
            new_z = jnp.where(masks[bx, by], val, f[:, :, op.target_z])
            start = op.target_z.indices(f.shape[2])[0]
            e[op.field_name] = jax.lax.dynamic_update_slice(
                f, new_z, (0, 0, start))
        return e

    return step


def default_mesh2d():
    """Largest 2-D mesh over the available devices (rows ~ sqrt)."""
    n = len(jax.devices())
    mx = int(np.sqrt(n))
    while n % mx:
        mx -= 1
    return make_mesh((mx, n // mx), ("data", "model"))


def run_sharded(program: Program, env: Dict[str, np.ndarray], mesh=None,
                use_pallas=None, time_tile=None, resident=None, *,
                options=None):
    """Execute a recorded WFA program on a 2-D device mesh.

    A thin wrapper over the unified engine: plans the program for the
    ``pallas`` (``use_pallas=True``; halo-pad brick → fused kernel inside
    the mapped function, ``time_tile=k`` amortizing one depth-``k·h``
    exchange over k steps) or ``jit`` backend and executes it inside one
    ``shard_map``.  Bodies that cannot be lowered fall back to
    :func:`interp_step_sharded` with a logged reason.  Fused bricks step
    halo-resident (standing padded brick buffers, margin-slab ppermute
    refresh via :func:`halo_refresh_rows`, donated entry buffers);
    ``resident=False`` forces the legacy repacking steps — both are bitwise
    identical.

    ``env`` maps field names to global ``(X, Y, Z)`` arrays; the returned
    env holds the final values, gathered back to host NumPy.  With
    ``mesh=None`` the default mesh covers all available devices (a single
    device degenerates to one brick, so the same script runs anywhere):

    >>> import numpy as np
    >>> from repro.core import WSE_Array, WSE_For_Loop, WSE_Interface
    >>> with WSE_Interface() as wse:
    ...     T = WSE_Array("T", init_data=np.full((8, 8, 4), 2.0, np.float32))
    ...     with WSE_For_Loop("time_loop", 2):
    ...         T[1:-1, 0, 0] = 0.5 * T[1:-1, 0, 0]
    >>> out = run_sharded(wse.program, {"T": T.init_data})
    >>> float(out["T"][3, 3, 1])
    0.5

    Execution policy can equivalently travel as one frozen bundle,
    ``options=RunOptions(...)`` — the legacy ``use_pallas=`` / ``time_tile=``
    / ``resident=`` keywords are deprecation shims that warn once and
    forward (``use_pallas=True`` maps to ``backend="pallas"``).
    """
    from repro.engine import execute, plan
    from repro.engine.options import UNSET, _warn_once, resolve_options

    options = resolve_options(
        options,
        "run_sharded",
        time_tile=UNSET if time_tile is None else time_tile,
        resident=UNSET if resident is None else resident,
    )
    if use_pallas is not None:
        _warn_once("run_sharded", "use_pallas", "backend='pallas'")
        options = options.replace(backend="pallas" if use_pallas else "jit")
    if mesh is None:
        mesh = options.mesh if options.mesh is not None else default_mesh2d()
    options = options.replace(
        backend=options.resolved_backend("jit"), mesh=mesh
    )
    p = plan(program, options)
    return execute(p, env)
