"""Codegen pass + kernel cache for the WFA program compiler.

``compile_group`` turns one loop body's lowered :class:`LoweredGroup` into a
``step(env) -> env`` function around exactly one fused ``pl.pallas_call``
(built by :func:`repro.kernels.fused.build_fused_call`).  Kernels are
memoized by *program signature* — the lowered tap form plus field
shapes/dtypes and interpret setting — so re-making an identical
program (the WFA's repeated ``make_WSE`` workflow) reuses the compiled
kernel; :data:`stats` exposes build/hit/fallback counters for tests and
benchmarks.

Two integration points:

* :func:`compile_group` — single device.  Inputs are wrap-padded with
  ``jnp.pad`` so out-of-domain taps reproduce the interpreter's ``jnp.roll``
  semantics bit-for-bit (wrap-around only ever lands in Moat cells for
  depth-1 stencils; for wider stencils the backends still agree because both
  wrap).
* :func:`compile_group_sharded` — inside ``shard_map``.  The brick's
  margin slabs cross by ICI ppermute (landed in a resident buffer, or in
  a ``core.halo.halo_pad`` repack) and the kernel's Moat mask is driven by
  the brick's mesh coordinates.
"""
from __future__ import annotations

import dataclasses
import logging
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.compiler.ir import LoweredGroup, LoweringError, lower_group

log = logging.getLogger("repro.compiler")


@dataclasses.dataclass
class CompilerStats:
    """Counters for the fused-kernel pipeline (reset with ``reset_stats``)."""

    groups_fused: int = 0      # loop bodies routed to a fused kernel
    kernels_built: int = 0     # distinct pallas_call sites constructed
    cache_hits: int = 0        # loop bodies served from the kernel cache
    fallbacks: int = 0         # loop bodies routed to the interpreter
    fallback_reasons: Tuple[str, ...] = ()

    def note_fallback(self, reason: str) -> None:
        self.fallbacks += 1
        self.fallback_reasons = self.fallback_reasons + (reason,)


stats = CompilerStats()

_KERNEL_CACHE: Dict[tuple, object] = {}


def reset_stats() -> None:
    # mutate in place so `from repro.compiler import stats` stays live
    stats.groups_fused = 0
    stats.kernels_built = 0
    stats.cache_hits = 0
    stats.fallbacks = 0
    stats.fallback_reasons = ()


def clear_cache() -> None:
    _KERNEL_CACHE.clear()


def try_compile(compile_fn, loop):
    """Shared fallback policy for both pallas backends (single + sharded).

    Runs ``compile_fn()``; on :class:`LoweringError` counts the fallback,
    logs the reason, and returns ``None`` so the caller substitutes its
    interpreter step.  Keeping the policy here stops the two call sites from
    diverging in accounting or log wording.
    """
    try:
        return compile_fn()
    except LoweringError as e:
        stats.note_fallback(str(e))
        log.warning(
            "pallas lowering failed for loop %r: %s — falling back to the "
            "interpreter for this body", getattr(loop, "name", None), e)
        return None


def _field_specs(group: LoweredGroup, shapes: Dict[str, tuple],
                 dtypes: Dict[str, object]):
    """Ordered name -> (nz, dtype); validates a common (X, Y) extent."""
    names = list(group.fields_written())
    for n in group.fields_read():
        if n not in names:
            names.append(n)
    base_xy = shapes[names[0]][:2]
    for n in names:
        if shapes[n][:2] != base_xy:
            raise LoweringError(
                f"fields {names[0]!r} {shapes[names[0]]} and {n!r} "
                f"{shapes[n]} disagree in (X, Y); cannot fuse")
    specs = {n: (shapes[n][2], dtypes[n]) for n in names}
    return specs, base_xy


def _get_kernel(group: LoweredGroup, specs, bx, by, nx, ny, interpret,
                time_tile, wrap, margin=0, batch=1, region=None):
    from repro.kernels.fused import build_fused_call
    sig = (group, tuple((n, s[0], jnp.dtype(s[1]).name) for n, s in
                        specs.items()), bx, by, nx, ny,
           bool(interpret), int(time_tile), bool(wrap), int(margin),
           int(batch), region)
    hit = _KERNEL_CACHE.get(sig)
    if hit is not None:
        stats.cache_hits += 1
        return hit
    # one cache entry per (signature, batch, region): the builder itself is
    # batch-independent (the per-member kernel is vmapped over the leading
    # axis at the step layer), but keying on B means one warm entry serves
    # the whole fleet of that ensemble width — the bench gate "one compile
    # per plan signature" stays truthful for batched plans.  ``region`` tags
    # the overlap scheduler's windowed interior launch (None = whole brick).
    kernel = build_fused_call(group.updates, specs, group.halo, bx, by,
                              nx, ny, interpret=interpret,
                              time_tile=time_tile, wrap=wrap, margin=margin,
                              region=region)
    stats.kernels_built += 1
    _KERNEL_CACHE[sig] = kernel
    return kernel


def compile_transfer(kind: str, fine_shape, coarse_shape, dtype,
                     interpret: bool = False):
    """Build (and cache) one inter-grid transfer kernel for a level pair.

    ``kind`` is ``"restrict"`` (full-weighting, fine → coarse) or
    ``"prolong"`` (trilinear, coarse → fine); the canonical form is
    :class:`repro.compiler.ir.TransferStencil`, which validates the shape
    pair, and the kernels live in :mod:`repro.kernels.transfer`.  Cached in
    the same signature-keyed kernel cache as the fused stencil kernels —
    one entry per (kind, level-pair shapes, dtype).
    """
    from repro.compiler.ir import TransferStencil
    from repro.kernels import transfer as ktransfer

    ts = TransferStencil(kind, tuple(fine_shape), tuple(coarse_shape))
    sig = ("transfer", ts, jnp.dtype(dtype).name, bool(interpret))
    hit = _KERNEL_CACHE.get(sig)
    if hit is not None:
        stats.cache_hits += 1
        return hit
    if kind == "restrict":
        kernel = ktransfer.build_restrict_call(
            ts.fine_shape, ts.coarse_shape, dtype, interpret=interpret)
    else:
        kernel = ktransfer.build_prolong_call(
            ts.coarse_shape, ts.fine_shape, dtype, interpret=interpret)
    stats.kernels_built += 1
    _KERNEL_CACHE[sig] = kernel
    return kernel


def _build_overlap_step(group, specs, bx, by, nx, ny, interpret,
                        time_tile, wrap, margin, batch, split,
                        coords_fn, slabs_fn):
    """One interior/boundary-split step for the exchange/compute overlap.

    The schedule both pallas backends share (single device substitutes wrap
    slabs for the ppermute exchange):

    1. **exchange in flight** — the depth-``k·h`` margin slabs are extracted
       (``slabs_fn``) into their own buffers, the *double-buffered margins*:
       the transfer never aliases the resident buffers the interior launch
       is writing in place (a region launch aliases its inputs; interpret
       mode only), so ``input_output_aliases`` stays valid.
    2. **interior launch** — the region at distance ``≥ k·h`` from every
       brick edge steps ``k`` sub-steps off a window contained in the brick:
       no margin reads, so nothing orders it after the exchange and the
       scheduler is free to run both concurrently.
    3. **boundary launches** — once the slabs land, each shell region's
       padded window is assembled from the **pre-step** buffers + landed
       slabs (:func:`repro.engine.layout.strip_window` — bitwise the window
       a refreshed monolithic launch would read) and stepped by its own
       small kernel; outputs splice into the written buffers.

    Every launch reuses the monolithic kernel machinery (same per-cell tap
    arithmetic, same Moat masking from global coordinates), which is why
    the split output is bitwise-equal to the fused monolithic kernel.
    """
    from repro.engine.layout import land_region, strip_window

    ph = time_tile * group.halo
    in_names = list(specs)
    interior, written = _get_kernel(group, specs, bx, by, nx, ny,
                                    interpret, time_tile, wrap,
                                    margin=margin, batch=batch,
                                    region=split.interior)
    shells = [
        _get_kernel(group, specs, r.rx, r.ry, nx, ny, interpret,
                    time_tile, wrap, margin=0, batch=batch)[0]
        for r in split.shells
    ]

    def _launch(kern, coords, ins):
        if batch > 1:
            return jax.vmap(lambda *a: kern(coords, *a))(*ins)
        return kern(coords, *ins)

    def step(env):
        env = dict(env)
        coords = coords_fn()
        slabs = {n: slabs_fn(env[n]) for n in in_names}
        ins = [env[n] for n in in_names]
        # pin the fusion boundary at the kernel inputs and the in-flight
        # slab buffers — the same barrier rule the monolithic paths use to
        # keep FMA contraction identical across margin producers
        flat = [s for n in in_names for s in slabs[n].values()]
        pinned = jax.lax.optimization_barrier(tuple(ins) + tuple(flat))
        ins = list(pinned[:len(in_names)])
        rest = iter(pinned[len(in_names):])
        slabs = {n: {key: next(rest) for key in slabs[n]} for n in in_names}
        ic = coords + jnp.array([[split.interior.x0, split.interior.y0]],
                                jnp.int32)
        outs = _launch(interior, ic, ins)
        new = dict(zip(in_names, ins))
        new.update(zip(written, outs))
        for r, kern in zip(split.shells, shells):
            wins = [strip_window(pre, slabs[n], margin, ph, r, bx, by)
                    for n, pre in zip(in_names, ins)]
            wins = list(jax.lax.optimization_barrier(tuple(wins)))
            sc = coords + jnp.array([[r.x0, r.y0]], jnp.int32)
            souts = _launch(kern, sc, wins)
            for name, so in zip(written, souts):
                new[name] = land_region(new[name], so, margin, r)
        env.update(new)
        return env

    return step


def compile_group(ops, shapes: Dict[str, tuple], dtypes: Dict[str, object],
                  interpret: bool = False, *,
                  time_tile: int = 1, group: LoweredGroup = None,
                  resident: int = 0, batch: int = 1, overlap: bool = False):
    """Lower + codegen one loop body for single-device execution.

    Returns ``step(env) -> env`` fusing all of ``ops`` into one pallas_call;
    with ``time_tile=k`` each call advances *k* steps off one wrap pad of
    depth ``k·h`` (validated by :func:`repro.compiler.ir.tile_group`).  Pass
    ``group=`` to reuse a lowering the planner already derived.  Raises
    :class:`LoweringError` when the body cannot be fused (the caller falls
    back to the interpreter and logs the reason).

    ``resident=K`` switches to the halo-resident protocol (the engine's
    :class:`~repro.engine.layout.HaloLayout`): ``env`` holds ``(nx + 2K,
    ny + 2K, nz)`` buffers, the step refreshes only the depth-``k·h`` wrap
    rows above and below the brick in place
    (:func:`repro.engine.layout.wrap_refresh_rows` — two edge slabs, no
    full-array repack; the kernel builds the Y halo from the rows it
    loads), and the kernel writes each written field into a fresh buffer
    of the resident extent (double-buffered; see
    :func:`repro.engine.executor.run_launches` for how the step loop keeps
    that copy-free).  Bitwise identical to the repacking step at every
    precision: the kernel sees the same window values
    ``jnp.pad(mode="wrap")`` would have built.

    ``batch=B`` compiles an *ensemble* step: every env buffer carries a
    leading ``(B, ...)`` axis, the margin refresh / wrap pad and the
    barrier operate on the stacked arrays directly (they are rank-agnostic
    over leading axes), and only the fused ``pallas_call`` is ``jax.vmap``-
    wrapped over the members — so one launch advances all B scenarios and
    each member's arithmetic is bitwise identical to its ``batch=1`` run.
    The step is **not** built by vmapping the whole batch=1 step: the
    barrier that pins the resident/legacy bitwise guarantee has no batching
    rule, so batching is threaded below it instead.

    ``overlap=True`` (resident mode only) splits the launch into an interior
    kernel + four boundary shell kernels so the margin refresh overlaps the
    interior compute (see :func:`_build_overlap_step`); bodies whose brick
    is too small for a nonempty interior (or halo-free bodies) silently keep
    the monolithic launch.
    """
    from repro.compiler.ir import split_regions, tile_group

    if group is None:
        group = lower_group(ops)
    specs, (nx, ny) = _field_specs(group, shapes, dtypes)
    # same brick bound the planner clamps against; direct callers get the
    # validation too (a wrap pad deeper than the grid would be ill-formed)
    tiled = tile_group(group, time_tile, brick_xy=(nx, ny))
    ph = tiled.halo            # k·h margin, paid once per tile
    if resident and resident < ph:
        raise LoweringError(
            f"resident margin {resident} < tiled halo {ph}")
    if overlap and resident:
        split = split_regions(group, time_tile, (nx, ny))
        if split is not None:
            from repro.engine.layout import wrap_slabs

            coords0 = jnp.zeros((1, 2), jnp.int32)
            step = _build_overlap_step(
                group, specs, nx, ny, nx, ny, interpret, time_tile,
                True, resident, batch, split,
                coords_fn=lambda: coords0,
                slabs_fn=lambda buf: wrap_slabs(buf, resident, ph))
            stats.groups_fused += 1
            return step
    fused, written = _get_kernel(group, specs, nx, ny, nx, ny,
                                 interpret, time_tile, wrap=True,
                                 margin=resident, batch=batch)
    in_names = list(specs)
    coords = jnp.zeros((1, 2), jnp.int32)
    call = (jax.vmap(lambda *a: fused(coords, *a)) if batch > 1
            else (lambda *a: fused(coords, *a)))
    stats.groups_fused += 1

    if resident:
        from repro.engine.layout import wrap_refresh_rows

        def step(env):
            env = dict(env)
            with jax.named_scope("wfa.engine.margin_refresh"):
                ins = [wrap_refresh_rows(env[n], resident, ph)
                       for n in in_names]
            # pin the fusion boundary at the kernel inputs: XLA otherwise
            # fuses the margin producer (refresh here, pad on the legacy
            # path) into the kernel's first ops, and the differing contexts
            # can flip FMA contraction — a ~1-ulp resident/legacy divergence.
            # Both paths barrier, so both compile the kernel identically and
            # the bitwise-equality guarantee holds at every precision.
            ins = list(jax.lax.optimization_barrier(tuple(ins)))
            outs = call(*ins)
            for name, inp in zip(in_names, ins):
                env[name] = inp  # refreshed margins (non-written fields)
            for name, out in zip(written, outs):
                env[name] = out
            return env

        return step

    def step(env):
        env = dict(env)
        padded = []
        with jax.named_scope("wfa.engine.wrap_pad"):
            for n in in_names:
                v = env[n]
                if ph:
                    widths = ((0, 0),) * (v.ndim - 3) + (
                        (ph, ph), (ph, ph), (0, 0))
                    v = jnp.pad(v, widths, mode="wrap")
                padded.append(v)
        padded = list(jax.lax.optimization_barrier(tuple(padded)))
        outs = call(*padded)
        for name, out in zip(written, outs):
            env[name] = out
        return env

    return step


def compile_group_sharded(ops, shapes: Dict[str, tuple],
                          dtypes: Dict[str, object], *, mesh_xy, axis_names,
                          interpret: bool = False,
                          time_tile: int = 1, group: LoweredGroup = None,
                          resident: int = 0, batch: int = 1,
                          overlap: bool = False):
    """Lower + codegen one loop body for use *inside* ``shard_map``.

    ``shapes`` are the global field shapes; the returned ``step`` operates on
    the per-device brick env (halo-pads it with ppermute — depth ``k·h``
    when ``time_tile=k``, ONE exchange per k steps — then runs the same
    fused kernel with mesh-derived coordinates).

    ``resident=K`` switches to the halo-resident protocol: the brick env
    holds ``(bx + 2K, by + 2K, nz)`` buffers, the exchange moves only the
    four depth-``k·h`` margin slabs (same ppermute traffic, no whole-brick
    repack), the two X slabs land in place and the two Y slabs go to the
    kernel as operands (:func:`repro.core.halo.halo_refresh_rows`), and the
    kernel writes double-buffered resident outputs, as on one device.
    Bitwise identical to the repacking step at every precision.

    ``overlap=True`` (resident mode only) splits each launch into an
    interior kernel — concurrent with the margin slabs' ``ppermute``
    exchange, which it does not depend on — plus four boundary shell
    kernels fed by the landed slabs (:func:`_build_overlap_step`).  Bricks
    too small for a nonempty interior keep the monolithic launch.
    """
    from repro.compiler.ir import split_regions, tile_group
    from repro.core.halo import exchange_slabs, halo_pad, halo_refresh_rows

    if group is None:
        group = lower_group(ops)
    specs, (nx, ny) = _field_specs(group, shapes, dtypes)
    mx, my = mesh_xy
    ax_x, ax_y = axis_names
    if nx % mx or ny % my:
        raise LoweringError(
            f"global extent ({nx},{ny}) not divisible by mesh ({mx},{my})")
    bx, by = nx // mx, ny // my
    tiled = tile_group(group, time_tile, brick_xy=(bx, by))
    ph = tiled.halo
    if resident and resident < ph:
        raise LoweringError(
            f"resident margin {resident} < tiled halo {ph}")

    def _coords():
        cx = jax.lax.axis_index(ax_x) * bx
        cy = jax.lax.axis_index(ax_y) * by
        return jnp.stack([cx, cy]).astype(jnp.int32).reshape(1, 2)

    if overlap and resident:
        split = split_regions(group, time_tile, (bx, by))
        if split is not None:
            step = _build_overlap_step(
                group, specs, bx, by, nx, ny, interpret, time_tile,
                False, resident, batch, split,
                coords_fn=_coords,
                slabs_fn=lambda buf: exchange_slabs(
                    buf, resident, ph, ax_x, ax_y, mx, my))
            stats.groups_fused += 1
            return step
    fused, written = _get_kernel(group, specs, bx, by, nx, ny,
                                 interpret, time_tile, wrap=False,
                                 margin=resident, batch=batch)
    in_names = list(specs)
    stats.groups_fused += 1

    def _call(coords, ins):
        # batched bricks: the exchange/barrier above already ran on the
        # stacked (B, ...) arrays; vmap only the per-member fused kernel
        # (coords are member-invariant, closed over)
        if batch > 1:
            return jax.vmap(lambda *a: fused(coords, *a))(*ins)
        return fused(coords, *ins)

    if resident:

        def step(env):
            env = dict(env)
            coords = _coords()
            ins, yslabs = [], []
            for n in in_names:
                buf = env[n]
                if ph:  # a halo-free body exchanges nothing
                    buf, lo_y, hi_y = halo_refresh_rows(buf, resident, ph,
                                                        ax_x, ax_y, mx, my)
                    yslabs += [lo_y, hi_y]
                ins.append(buf)
            pinned = jax.lax.optimization_barrier(tuple(ins) + tuple(yslabs))
            outs = _call(coords, list(pinned))
            for name, inp in zip(in_names, pinned[:len(ins)]):
                env[name] = inp
            for name, out in zip(written, outs):
                env[name] = out
            return env

        return step

    def step(env):
        env = dict(env)
        coords = _coords()
        padded = [env[n] if ph == 0 else
                  halo_pad(env[n], ph, ax_x, ax_y, mx, my)
                  for n in in_names]
        padded = list(jax.lax.optimization_barrier(tuple(padded)))
        outs = _call(coords, padded)
        for name, out in zip(written, outs):
            env[name] = out
        return env

    return step
