"""The program's own names for its work: device scopes in the compiled
programs, host spans on the profiler's and the host's clocks, and the
engine's counters on the runner's fast path."""
import glob
import os
import re
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from conftest import heat_init
from repro.core import WSE_Array, WSE_For_Loop, WSE_Interface
from repro.engine import (
    RunOptions,
    device_scopes,
    execute,
    plan,
    reset_stats,
    single_runner,
    span,
    spans,
    stats,
)
from repro.solver.api import make_solver
from repro.solver.presets import btcs_program

_stats_module = sys.modules["repro.engine.stats"]
SHAPE = (16, 16, 8)


def _heat_plan(steps=4, **options):
    wse = WSE_Interface()
    T = WSE_Array("T_n", init_data=heat_init(SHAPE))
    with WSE_For_Loop("t", steps):
        T[1:-1, 0, 0] = 0.4 * T[1:-1, 0, 0] + 0.1 * (
            T[2:, 0, 0] + T[:-2, 0, 0] + T[1:-1, 1, 0]
            + T[1:-1, 0, -1] + T[1:-1, -1, 0] + T[1:-1, 0, 1]
        )
    try:
        return plan(wse.program, RunOptions(backend="pallas", **options))
    finally:
        wse.__exit__()


def _innermost(line):
    name = re.search(r'op_name="([^"]*)"', line)
    found = re.findall(r"wfa\.[a-z0-9_]+\.[a-z0-9_]+", name.group(1)) if name else []
    return found[-1] if found else None


@pytest.mark.parametrize("case, expected", [
    ("legacy", {"wfa.engine.wrap_pad", "wfa.kernel.stencil"}),
    ("resident", {"wfa.engine.margin_refresh", "wfa.engine.layout",
                  "wfa.kernel.stencil"}),
    ("cg", {"wfa.krylov.dot", "wfa.krylov.update", "wfa.engine.wrap_pad",
            "wfa.kernel.stencil"}),
])
def test_compiled_programs_carry_scopes_that_device_scopes_maps(case, expected):
    """Each program's compiled text names its work, and ``device_scopes``
    maps every instruction it keys to the innermost scope on that line."""
    before = list(_stats_module._programs.keys())
    if case == "cg":
        keep = make_solver(btcs_program(SHAPE, 0.1), "T", method="cg")
    else:
        p = _heat_plan(resident=case == "resident")
        assert (p.layout.pad > 0) == (case == "resident")
        keep = single_runner(p)
    ((jitted, args),) = [(f, a) for f, a in _stats_module._programs.items()
                         if all(f is not b for b in before)]
    text = jitted.lower(*args).compile().as_text()
    scopes = device_scopes()
    mapped = set()
    for line in text.splitlines():
        key = _stats_module.instruction_key(line)
        if key in scopes:
            assert scopes[key] == _innermost(line), line
            mapped.add(scopes[key])
    assert expected <= mapped, (case, mapped)
    del keep


def test_spans_nest_and_reset_clears_them():
    reset_stats()
    with span("wfa.test.outer"):
        with span("wfa.test.inner"):
            pass
    (inner, outer) = spans()
    assert (inner[0], inner[3]) == ("wfa.test.inner", "wfa.test.outer")
    assert (outer[0], outer[3]) == ("wfa.test.outer", None)
    assert outer[1] <= inner[1] <= inner[2] <= outer[2]
    reset_stats()
    assert spans() == []


@pytest.mark.parametrize("entry", ["runner", "execute", "solver"])
def test_dispatch_spans(entry):
    """The runner's call, ``execute`` and a solve are spans of their own,
    the solver's entry copy a child of its dispatch."""
    reset_stats()
    if entry == "solver":
        fn = make_solver(btcs_program(SHAPE, 0.1), "T", method="cg")
        jax.block_until_ready(fn(jnp.asarray(heat_init(SHAPE))))
        want = [("wfa.solver.copy_x0", "wfa.solver.dispatch"),
                ("wfa.solver.dispatch", None)]
    elif entry == "runner":
        run = single_runner(_heat_plan())
        jax.block_until_ready(run({"T_n": jnp.asarray(heat_init(SHAPE))}))
        want = [("wfa.engine.dispatch", None)]
    else:
        execute(_heat_plan(), {"T_n": heat_init(SHAPE)})
        want = [("wfa.engine.dispatch", "wfa.engine.execute"),
                ("wfa.engine.execute", None)]
    assert [(n, parent) for n, _, _, parent in spans()] == want


@pytest.mark.parametrize("entry", ["runner", "execute", "guarded"])
def test_runner_call_counts_the_plan_once(entry):
    """A direct call of the runner moves the counters by the plan's counts,
    and ``execute`` (which calls it) counts the same, not twice; so does
    its guarded path, which counts for itself."""
    p = _heat_plan(steps=6, time_tile=2)
    seg = p.segments[0]
    assert (seg.n_steps, seg.time_tile) == (6, 2)
    run = single_runner(p)
    reset_stats()
    if entry == "runner":
        jax.block_until_ready(run({"T_n": jnp.asarray(heat_init(SHAPE))}))
    elif entry == "execute":
        execute(p, {"T_n": heat_init(SHAPE)})
    else:
        execute(p, {"T_n": heat_init(SHAPE)}, RunOptions(check_finite=2))
    assert (stats.steps_run, stats.launches, stats.exchanges) == (6, 3, 3)


class _Compiled:
    """Stands in for a jitted function whose compiled text is ``text``."""

    def __init__(self, text):
        self.text = text

    def lower(self, *args):
        return self

    def compile(self):
        return self

    def as_text(self):
        return self.text


def _line(name, scope):
    meta = f' metadata={{op_name="jit(run)/{scope}/mul"}}' if scope else ""
    return f"  %{name} = f32[8,8]{{1,0}} fusion(f32[8,8]{{1,0}} %p), kind=kLoop{meta}"


@pytest.mark.parametrize("other, kept", [
    ("wfa.krylov.dot", True),  # both programs agree
    ("wfa.krylov.update", False),  # two scopes for one key
    (None, False),  # one program leaves the key unscoped
])
def test_device_scopes_drops_keys_programs_disagree_on(monkeypatch, other, kept):
    """Instruction names are unique only within one program: a key two
    recorded programs map differently is left out of ``device_scopes``."""
    dot = "wfa.krylov.dot"
    a = _Compiled(_line("fusion.1", dot) + "\n" + _line("fusion.2", dot))
    b = _Compiled(_line("fusion.1", other))
    monkeypatch.setattr(_stats_module, "_programs", {a: (), b: ()})
    want = {("fusion.2", ("f32[8,8]",)): "wfa.krylov.dot"}
    if kept:
        want[("fusion.1", ("f32[8,8]",))] = "wfa.krylov.dot"
    assert device_scopes() == want


def test_span_ring_agrees_with_the_profiler_trace(tmp_path):
    """Each span's ring record and its ``TraceAnnotation`` event in a
    profiler trace agree within 50 us once put on one clock through an
    anchor span."""
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        reset_stats()
        with span("wfa.test.anchor"):
            pass
        for _ in range(5):
            with span("wfa.test.work"):
                time.sleep(0.01)
            time.sleep(0.005)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"), recursive=True)
    events = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("wfa.test."):
                    events.setdefault(e.name, []).append((e.start_ns, e.end_ns))
    ring = spans()
    anchor = next(r for r in ring if r[0] == "wfa.test.anchor")
    offset = events["wfa.test.anchor"][0][0] - anchor[1]
    work = sorted(events["wfa.test.work"])
    mine = [(s + offset, e + offset) for n, s, e, _ in ring if n == "wfa.test.work"]
    assert len(work) == len(mine) == 5
    for (ts, te), (rs, re_) in zip(work, mine):
        assert abs(ts - rs) < 50e3 and abs(te - re_) < 50e3


MESH_CHILD = r'''
import json
import jax, jax.numpy as jnp
from repro.core.jaxcompat import make_mesh
from repro.engine import (RunOptions, device_scopes, plan, reset_stats,
                          sharded_runner, spans, stats)
from repro.engine.stats import _programs, instruction_key
from repro.service.workloads import _record_heat3d

mesh = make_mesh((2, 2), ("x", "y"))
out = {}
for resident in (False, True):
    program, _ = _record_heat3d((32, 32, 8), jnp.float32, 4)
    p = plan(program, RunOptions(backend="pallas", mesh=mesh, time_tile=2,
                                 resident=resident))
    before = list(_programs)
    run, sharding = sharded_runner(p)
    (new,) = [f for f in _programs if all(f is not b for b in before)]
    env = {n: jax.device_put(jnp.ones(f.shape, f.dtype), sharding)
           for n, f in program.fields.items()}
    reset_stats()
    jax.block_until_ready(run(env))
    text = new.lower(*_programs[new]).compile().as_text()
    scopes = device_scopes()
    keys = map(instruction_key, text.splitlines())
    mapped = sorted({scopes[k] for k in keys if k in scopes})
    out["resident" if resident else "repack"] = {
        "spans": [[n, parent] for n, _, _, parent in spans()],
        "mapped": mapped, "halo_bytes": stats.halo_bytes,
        "launches": stats.launches, "pad": p.layout.pad}
print("RESULT " + json.dumps(out))
'''


@pytest.fixture(scope="module")
def mesh_runs():
    """One child process on four virtual CPU devices (the test process
    keeps one) runs a 2x2 mesh runner on each layout."""
    import json
    import subprocess

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(root, "src"))
    p = subprocess.run([sys.executable, "-c", MESH_CHILD], capture_output=True,
                       text=True, timeout=600, env=env)
    assert p.returncode == 0, p.stderr[-4000:]
    (line,) = [s for s in p.stdout.splitlines() if s.startswith("RESULT ")]
    return json.loads(line[len("RESULT "):])


@pytest.mark.parametrize("layout, expected", [
    ("repack", {"wfa.halo.exchange", "wfa.halo.pad", "wfa.kernel.stencil"}),
    ("resident", {"wfa.halo.exchange", "wfa.engine.margin_refresh",
                  "wfa.engine.layout", "wfa.kernel.stencil"}),
])
def test_sharded_runner_spans_scopes_and_halo_bytes(mesh_runs, layout, expected):
    """A call of ``sharded_runner``'s runner is one ``wfa.engine.dispatch``
    span; its program is recorded, and ``device_scopes`` maps its
    instructions to the halo's scopes; ``halo_bytes`` moves by one X slab
    (2, 16, 8) and one Y slab (20, 2, 8) of float32 a launch (k=2, 16x16x8
    bricks, two launches)."""
    r = mesh_runs[layout]
    assert r["spans"] == [["wfa.engine.dispatch", None]]
    assert expected <= set(r["mapped"]), r["mapped"]
    assert (r["pad"] > 0) == (layout == "resident")
    assert r["launches"] == 2
    assert r["halo_bytes"] == 2 * 4 * (2 * 16 * 8 + 20 * 2 * 8)
