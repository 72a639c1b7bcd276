"""The readers of the program's own names: the split of device busy time
by scope, the host time in the program's dispatch spans, and the five
metrics that read them, on a hand-made trace and ring and on short windows
of both cells recorded on a TPU v5e."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.harness import cell, generator, manifest, scopes  # noqa: E402
from bench.harness import trace as tr  # noqa: E402
from bench.harness.trace import Event, Trace  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
KERNEL = ('%wfa_stencil.3 = f32[512,512,128]{2,1,0} custom-call(f32[516,516,128]{2,1,0} '
          '%p), custom_call_target="tpu_custom_call"')
PAD = '%fusion.6 = f32[516,516,128]{2,1,0} fusion(f32[512,512,128]{2,1,0} %x), kind=kLoop'
COPY = '%copy.1 = f32[512,512,128]{2,1,0} copy(f32[512,512,128]{2,1,0} %a)'
WHILE = '%while = (s32[]) while((s32[]) %t), body=%b'
MADE_SCOPES = {("wfa_stencil.3", ("f32[512,512,128]",)): "wfa.kernel.stencil",
               ("fusion.6", ("f32[516,516,128]",)): "wfa.engine.wrap_pad"}

#: per cell: the metrics that read the program's names
READERS = {
    "heat3d.explicit": ["engine.margin_share.explicit", "engine.dispatch_us.explicit"],
    "btcs.cg": ["engine.margin_share.solve", "krylov.dot_update_share",
                "solver.dispatch_us.solve"],
}
ALL = [m for ms in READERS.values() for m in ms]
#: the span each dispatch reader reads
DISPATCH = {"engine.dispatch_us.explicit": "wfa.engine.dispatch",
            "solver.dispatch_us.solve": "wfa.solver.dispatch"}


def _made():
    """Window 0-100 ns: a while around a kernel (10-50), a pad (50-80) and
    a copy no scope names (80-85)."""
    dev = [Event(WHILE, 10, 90), Event(KERNEL, 10, 50), Event(PAD, 50, 80),
           Event(COPY, 80, 85)]
    host = [Event("bench.window", 0, 100)]
    return Trace(devices={"/device:TPU:0": dev}, host=host)


def _ctx(trace, start_s=0.0, units=2):
    window = generator.Window(start_s, start_s + 1.0,
                              [generator.Unit(0.0, 0.0, True)] * units)
    return cell.Context(cell={}, config={}, traffic={}, window=window, setup_s=0.0,
                        kind="TPU v5 lite", info={"time_tile": 1}, trace=trace)


def test_bench_scopes_key_is_the_programs_key():
    from bench.harness import hlo
    from repro.engine.stats import instruction_key

    for text in (KERNEL, PAD, COPY):
        assert scopes.key(hlo.parse(text)) == instruction_key(text)


def test_bench_scopes_split_and_share_on_a_made_trace():
    t = _made()
    per, off, busy = scopes.split(t, MADE_SCOPES)
    assert per == pytest.approx({"wfa.kernel.stencil": 40e-9, "wfa.engine.wrap_pad": 30e-9})
    assert off == pytest.approx({"copy copy.1": 5e-9})  # unknown: unscoped
    assert busy == pytest.approx(75e-9)
    assert scopes.share(t, MADE_SCOPES, scopes.MARGIN) == pytest.approx(100 * 30 / 75)
    assert scopes.share(t, MADE_SCOPES, scopes.KRYLOV) is None
    note = scopes.note_split(t, MADE_SCOPES)
    assert "unscoped copy copy.1" in note and "(+0.0000 %)" in note


@pytest.mark.parametrize("metric", sorted(DISPATCH))
def test_bench_scopes_dispatch_us_on_a_made_ring(monkeypatch, metric):
    """A dispatch reader sums the host us of its spans that open in the
    window (times on the window's clock) over the window's units; spans
    before or after the window, and other names, do not count."""
    name = DISPATCH[metric]
    ring = [(name, 500, 9_000, None),  # opens before the window
            (name, 1_100, 4_100, None), ("wfa.child", 1_500, 2_500, name),
            ("wfa.other", 1_000, 90_000, None), (name, 50_000, 56_000, None),
            (name, 2_000_000_000, 2_000_001_000, None)]  # after it
    monkeypatch.setattr(scopes, "ring", lambda: ring)
    ctx = _ctx(None, start_s=1e-6, units=2)  # window 1000 ns to 1 s + 1000 ns
    assert _read(metric, ctx) == pytest.approx((3.0 + 6.0) / 2)
    assert ctx.notes["dispatch"] == (f"2 {name} spans in the window, 4.500 us each; "
                                     "wfa.child 1.000 us each")


def _recorded(name):
    trace = Trace.read(DATA / f"{name}.scoped.trace.json")
    doc = json.loads((DATA / f"{name}.program.json").read_text())
    smap = {(n, tuple(shapes)): s for n, shapes, s in doc["scopes"]}
    ring = [tuple(r) for r in doc["spans"]]
    return trace, doc, ring, smap


def _read(name, ctx):
    return manifest.reader(name)(ctx)


@pytest.mark.parametrize("name", sorted(READERS))
def test_bench_scopes_recorded_readers_read_the_chips_values(monkeypatch, name):
    """The readers, fed the trace, ring and scope map of a short window on
    the chip, read what the share readers read there and what the ring
    gives the dispatch readers; the split adds up to busy time."""
    trace, doc, ring, smap = _recorded(name)
    monkeypatch.setattr(scopes, "scope_map", lambda: smap)
    monkeypatch.setattr(scopes, "ring", lambda: ring)
    ctx = _ctx(trace, doc["window_start_s"], doc["units"])
    for metric in READERS[name]:
        assert _read(metric, ctx) == pytest.approx(doc["metrics"][metric], rel=1e-9)
    per, off, busy = scopes.split(trace, smap)
    assert sum(per.values()) + sum(off.values()) == pytest.approx(busy, rel=1e-3)
    scoped = sum(t for s, t in per.items()
                 if s in scopes.MARGIN + scopes.KRYLOV + scopes.KERNEL)
    assert scoped >= 0.95 * busy
    assert "sum" in ctx.notes["scopes"]
    assert ctx.notes["dispatch"].startswith(f"{doc['units']} wfa.")


@pytest.mark.parametrize("name", sorted(READERS))
def test_bench_scopes_recorded_unknown_ops_are_unscoped(name):
    """An operation the scope map does not know counts as unscoped: taking
    the kernel's keys out of the map moves its time out of every scope."""
    trace, _, _, smap = _recorded(name)
    per, off, busy = scopes.split(trace, smap)
    less = {k: s for k, s in smap.items() if s != "wfa.kernel.stencil"}
    per2, off2, busy2 = scopes.split(trace, less)
    assert "wfa.kernel.stencil" in per and "wfa.kernel.stencil" not in per2
    assert busy2 == busy
    assert sum(off2.values()) == pytest.approx(sum(off.values()) + per["wfa.kernel.stencil"])
    assert any(n.startswith("custom-call wfa_stencil.") for n in off2)


@pytest.mark.parametrize("name", sorted(READERS))
def test_bench_scopes_recorded_kernel_roofline_finds_the_named_kernel(name):
    """``fused_kernel_roofline.*`` finds the renamed kernel by its shape:
    the same launches and bytes as the operations under the kernel scope,
    and the share the chip read."""
    from bench.harness import hlo

    trace, doc, _, smap = _recorded(name)
    lo, hi = trace.window()
    named = [o for d in trace.devices for o in tr.ops(trace, d)
             if smap.get(scopes.key(o.ins)) == "wfa.kernel.stencil"
             and o.start >= lo and o.end <= hi]
    assert named and all(o.ins.name.startswith("wfa_stencil.") for o in named)
    r = tr.kernel_roofline(trace, 819e9)
    assert r["launches"] == len(named)
    assert r["bytes"] == sum(hlo.launch_bytes(o.ins) for o in named)
    roofline = "fused_kernel_roofline." + ("explicit" if name == "heat3d.explicit"
                                           else "solve")
    assert r["share"] == pytest.approx(doc["metrics"][roofline], rel=1e-9)


@pytest.mark.parametrize("metric", ALL)
def test_bench_scopes_readers_read_none_without_a_trace_or_names(monkeypatch, metric):
    """Untraced, a share reader reads ``None``, and a dispatch reader does
    where no span of its name opened in the window; traced, every reader
    reads ``None`` on a program that names nothing (an older commit)."""
    monkeypatch.setattr(scopes, "ring", lambda: [("wfa.other", 0, 10, None)])
    assert _read(metric, _ctx(None)) is None
    monkeypatch.setattr(scopes, "scope_map", lambda: None)
    monkeypatch.setattr(scopes, "ring", lambda: None)
    assert _read(metric, _ctx(_made())) is None


def test_bench_scopes_program_reads_the_programs_ring_and_map(monkeypatch):
    """On the CPU: the program's ring and scope map after one runner call."""
    import jax
    import jax.numpy as jnp

    from repro.engine import RunOptions, plan, reset_stats, single_runner
    from bench.schemes.ftcs import fig3_program

    monkeypatch.setattr(scopes, "_seen", [])
    run = single_runner(plan(fig3_program((16, 16, 8), 0.1, 4, "float32"),
                             RunOptions(backend="pallas")))
    reset_stats()
    jax.block_until_ready(run({"T": jnp.ones((16, 16, 8), jnp.float32)}))
    ring, smap = scopes.ring(), scopes.scope_map()
    assert [r[0] for r in ring] == ["wfa.engine.dispatch"]
    assert "wfa.kernel.stencil" in set(smap.values())
    assert scopes.scope_map() is smap
