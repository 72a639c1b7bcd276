"""Run one cell once and build its result line."""
from __future__ import annotations

import dataclasses
import gc
import glob
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Optional

from . import device, generator, manifest
from .manifest import ROOT


@dataclasses.dataclass
class Context:
    """What a metric reader may read."""

    cell: dict
    config: dict
    traffic: dict
    window: generator.Window
    setup_s: float
    kind: str
    info: dict
    trace: Optional[object] = None
    root: Path = ROOT
    notes: dict = dataclasses.field(default_factory=dict)

    @property
    def peaks(self) -> dict:
        return device.peaks(self.kind, self.root)


def _enable_compile_cache() -> str:
    """The program's persistent compile cache (``<checkout>/.jax_cache``
    unless ``JAX_COMPILATION_CACHE_DIR`` names another), holding every
    program however quickly it compiled, so only a cell's first run in a
    checkout compiles."""
    import jax
    from repro.compile_cache import enable_compile_cache

    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def _traced_window(loop, seconds: float):
    import jax

    from . import trace as tr

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            window = loop.window(seconds)
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
        return window, tr.load(path)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _host_log(window: generator.Window, log) -> None:
    """The slowest units and Python's collections inside the window, so a
    host stall can be told apart from a collection."""
    took = sorted(u.end - u.start for u in window.units)
    slow = sorted(window.units, key=lambda u: u.start - u.end)[:3]
    log(f"window {window.seconds:.3f} s: attempted={window.attempted} "
        f"failed={window.failed} compiles_in_window={window.compiles}; unit ms "
        f"min {took[0] * 1e3:.3f} median {took[len(took) // 2] * 1e3:.3f} "
        f"slowest " + ", ".join(f"{(u.end - u.start) * 1e3:.3f} at "
                                f"{u.start - window.start:.3f} s" for u in slow))
    pauses = generator.GcPauses.between(window.start, window.end)
    per_gen = [sum(1 for p in pauses if p[2] == k) for k in range(3)]
    longest = max((p[1] - p[0] for p in pauses), default=0.0)
    log(f"gc in window: {len(pauses)} collections (generation 0/1/2: "
        f"{per_gen[0]}/{per_gen[1]}/{per_gen[2]}), "
        f"{sum(p[1] - p[0] for p in pauses) * 1e3:.3f} ms in all, "
        f"longest {longest * 1e3:.3f} ms")


def run(name: str, seed: int, seconds: float, traced: bool, *,
        t_process: float, root: Path = ROOT, system: str = "program",
        require_tpu: bool = True, cache: bool = True, log=print) -> dict:
    """One run of cell ``name``: set-up, the window, the check, the metrics.

    ``require_tpu=False``, ``cache=False`` and ``system="control"`` exist for
    the tests and for the control runs; the benchmark's own runs use none.

    Set-up ends with a full collection and ``gc.freeze()``: the objects that
    loading, tracing and compiling left behind move out of the collector's
    reach, so a collection inside the window walks only what the window
    made."""
    import jax

    doc = manifest.load(root)
    cell = manifest.cell(doc, name)
    config = manifest.config(doc, cell["config"], root)
    traffic = manifest.traffic(cell["traffic"], root)
    chips = int(cell["chips"])
    devices = device.require_chips(chips) if require_tpu else jax.devices()[:chips]
    dev = device.describe(devices)
    log(f"cell {name}: device_kind={dev['kind']!r} platform={dev['platform']} "
        f"count={dev['count']} seed={seed} seconds={seconds} trace={int(traced)} "
        f"(devices up {time.perf_counter() - t_process:.3f} s after start)")
    cache = _enable_compile_cache() if cache else "off"
    generator.Compiles.install()
    generator.GcPauses.install()
    loop = manifest.loop(traffic, root)(config, traffic, seed,
                                        manifest.scheme(config, root),
                                        system == "control", devices)
    loop.setup()
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_process
    log(f"set-up {setup_s:.3f} s ({generator.Compiles.n} executables built or "
        f"loaded; compile cache {cache}); {json.dumps(loop.info)}")
    trace = None
    if traced:
        window, trace = _traced_window(loop, seconds)
    else:
        window = loop.window(seconds)
    _host_log(window, log)
    peak = device.memory_peak(devices)
    checks = _limits(loop.check(window), config.get("check", {}))
    ctx = Context(cell=cell, config=config, traffic=traffic, window=window,
                  setup_s=setup_s, kind=dev["kind"], info=loop.info, trace=trace,
                  root=root)
    metrics = {}
    for m in manifest.metrics(doc, name, traced):
        value = manifest.reader(m["name"], root)(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    for key, note in ctx.notes.items():
        log(f"{key}: {note}")
    correct = bool(window.done) and all(v is not None and v <= lim
                                        for v, lim in checks.values())
    dev["memory_peak_bytes"] = peak
    result = {"correct": correct, "attempted": window.attempted,
              "failed": window.failed, "metrics": metrics, "device": dev}
    if trace is not None:
        from . import trace as tr

        dev["busy_s"] = tr.busy_s(trace)
        dev["window_s"] = tr.window_s(trace)
        result["breakdown"] = {"device_ops": tr.top_ops(trace),
                               "idle_gaps": tr.idle_gaps(trace)}
    result["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    return result


def _limits(numbers: dict, limits: dict) -> dict:
    """Each limit of the configuration's ``check`` with the number read for
    it (``None`` where nothing was read, which is not correct); a number
    with no limit is an error."""
    stray = set(numbers) - set(limits)
    if stray:
        raise KeyError(f"no limit in the configuration's check for {sorted(stray)}")
    return {k: (numbers.get(k), lim) for k, lim in limits.items()}


def main(argv=None, t_process: float | None = None) -> int:
    import argparse

    t_process = time.perf_counter() if t_process is None else t_process
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args.workload, args.seed, args.seconds, bool(args.trace),
                     t_process=t_process, log=lambda s: print(s, flush=True))
    except device.NoAccelerator as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    for k, c in result["checks"].items():
        print(f"check {k} = {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
