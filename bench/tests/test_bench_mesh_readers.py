"""The readers of ``heat3d.mesh2x2``: the exposed share of the halo
exchange, the margin share with the mesh's repack, the kernel's roofline,
idle time and dispatch, on a hand-made trace of four chips."""
import gzip
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.harness import cell, generator, hlo, manifest, parsed, scopes  # noqa: E402
from bench.harness import trace as tr  # noqa: E402
from bench.harness.trace import Event, Trace  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"

CELL = "heat3d.mesh2x2"
KERNEL = ('%wfa_stencil.2 = f32[512,512,128]{2,1,0} custom-call(s32[1,2]{1,0} %c, '
          'f32[516,516,128]{2,1,0} %p), custom_call_target="tpu_custom_call"')
EXCHANGE = ('%collective-permute-start.2 = (f32[516,2,128]{2,1,0}, '
            'f32[516,2,128]{2,1,0}, u32[], u32[]) collective-permute-start('
            'f32[516,2,128]{2,1,0} %fusion.7), source_target_pairs={{0,1}}')
PAD = ('%pad_maximum_fusion.8 = f32[516,516,128]{2,1,0} fusion(f32[516,512,128]{2,1,0} '
       '%a, f32[516,2,128]{2,1,0} %b), kind=kLoop')
WHILE = '%while.10 = (s32[]) while((s32[]) %t), body=%b'
MADE_SCOPES = {scopes.key(hlo.parse(KERNEL)): "wfa.kernel.stencil",
               scopes.key(hlo.parse(EXCHANGE)): "wfa.halo.exchange",
               scopes.key(hlo.parse(PAD)): "wfa.halo.pad"}
#: per chip, in ns of a 0-100 ns window: exposed exchange time and busy time
EXPECTED = {"/device:TPU:0": (10, 70), "/device:TPU:1": (20, 60),
            "/device:TPU:2": (0, 40), "/device:TPU:3": (10, 70)}
READERS = ["halo.exposed_collective_share", "engine.margin_share.mesh2x2",
           "fused_kernel_roofline.mesh2x2", "device_idle_share.mesh2x2",
           "engine.dispatch_us.mesh2x2"]


def _made(exchange=True):
    """Chip 0 (and 3): kernel 10-50, exchange 40-60 (half under the
    kernel), pad 60-80, all inside a while.  Chip 1: exchange 0-20 alone,
    kernel 20-60.  Chip 2: kernel 20-60, no exchange."""
    ex = EXCHANGE if exchange else EXCHANGE.replace("start.2", "start.9")
    a = [Event(WHILE, 5, 90), Event(KERNEL, 10, 50), Event(ex, 40, 60),
         Event(PAD, 60, 80)]
    devices = {"/device:TPU:0": a, "/device:TPU:3": list(a),
               "/device:TPU:1": [Event(ex, 0, 20), Event(KERNEL, 20, 60)],
               "/device:TPU:2": [Event(KERNEL, 20, 60)]}
    return Trace(devices=devices, host=[Event("bench.window", 0, 100)])


def _ctx(trace):
    window = generator.Window(0.0, 1.0, [generator.Unit(0.0, 0.0, True)] * 2)
    return cell.Context(cell={}, config={}, traffic={"chunk_steps": 64},
                        window=window, setup_s=0.0, kind="TPU v5 lite",
                        info={"time_tile": 2, "halo_bytes_per_launch": 1052672.0},
                        trace=trace)


def _read(name, ctx):
    return manifest.reader(name)(ctx)


def test_bench_mesh_exposed_share_counts_only_the_exchange_alone(monkeypatch):
    """Exchange time under a kernel is hidden, exchange time alone is
    exposed; each chip's share is over its own busy time, and the metric
    is the mean over the chips, a chip with no exchange counting 0."""
    monkeypatch.setattr(scopes, "scope_map", lambda: MADE_SCOPES)
    ctx = _ctx(_made())
    want = [100.0 * a / b for a, b in EXPECTED.values()]
    assert _read("halo.exposed_collective_share", ctx) == pytest.approx(sum(want) / 4)
    note = ctx.notes["halo_exchange"]
    for d, (a, b) in EXPECTED.items():
        assert f"{d} {100.0 * a / b:.4f} %" in note
    assert "1052672.0 bytes sent a chip a launch, 32 exchanges a chunk" in note


@pytest.mark.parametrize("scope_map", ["none", "no_exchange_ran"])
def test_bench_mesh_exposed_share_reads_none_without_the_scope(monkeypatch, scope_map):
    """A program that names nothing, or a trace in which no operation
    under ``wfa.halo.exchange`` ran, reads ``None`` and not 0."""
    monkeypatch.setattr(scopes, "scope_map",
                        lambda: None if scope_map == "none" else MADE_SCOPES)
    trace = _made(exchange=scope_map == "none")
    assert _read("halo.exposed_collective_share", _ctx(trace)) is None


def test_bench_mesh_margin_share_counts_the_repack(monkeypatch):
    """``wfa.halo.pad`` counts toward the margin share: 20 ns on chips 0
    and 3, over 70 + 60 + 40 + 70 ns of busy time."""
    monkeypatch.setattr(scopes, "scope_map", lambda: MADE_SCOPES)
    ctx = _ctx(_made())
    assert _read("engine.margin_share.mesh2x2", ctx) == pytest.approx(100 * 40 / 240)
    assert "wfa.halo.pad" in ctx.notes["scopes"]


def test_bench_mesh_idle_share_is_the_mean_with_the_worst_chip_noted():
    ctx = _ctx(_made())
    idle = [100.0 - b for _, b in EXPECTED.values()]
    assert _read("device_idle_share.mesh2x2", ctx) == pytest.approx(sum(idle) / 4)
    assert ctx.notes["device_idle"] == "worst chip /device:TPU:2 60.0000 %"


def test_bench_mesh_kernel_roofline_sums_every_chip():
    """One launch on each of four chips, each moving its operands and
    result, over their summed device time."""
    ctx = _ctx(_made())
    moved = 4 * hlo.launch_bytes(hlo.parse(KERNEL))
    seconds = 4 * 40e-9
    assert _read("fused_kernel_roofline.mesh2x2", ctx) == pytest.approx(
        100.0 * moved / 819e9 / seconds)
    assert ctx.notes["fused_kernel"].startswith("4 launches over 4 chips")


@pytest.mark.parametrize("metric", READERS)
def test_bench_mesh_readers_read_none_without_a_trace_or_names(monkeypatch, metric):
    """Untraced, and traced on a program that names nothing (an older
    commit, with no mesh span and no recorded mesh program), each reader
    reads ``None`` or, where it needs no name, a number."""
    monkeypatch.setattr(scopes, "ring", lambda: [("wfa.other", 0, 10, None)])
    assert _read(metric, _ctx(None)) is None
    monkeypatch.setattr(scopes, "scope_map", lambda: None)
    monkeypatch.setattr(scopes, "ring", lambda: None)
    value = _read(metric, _ctx(_made()))
    if metric in ("fused_kernel_roofline.mesh2x2", "device_idle_share.mesh2x2"):
        assert value is not None
    else:
        assert value is None


def test_bench_mesh_manifest_reports_the_five_readers():
    doc = manifest.load()
    traced = {m["name"] for m in manifest.metrics(doc, CELL, True)}
    assert traced == set(READERS)
    untraced = {m["name"] for m in manifest.metrics(doc, CELL, False)}
    assert untraced == {"setup_s", "cell_updates_per_s"}
    assert manifest.cell(doc, CELL)["chips"] == 4


@pytest.mark.parametrize("name", ["heat3d.explicit", "btcs.cg"])
def test_bench_parsed_reads_what_the_harness_reads(name):
    """On windows recorded on the chip, the parse-once path gives the
    numbers and the note of ``scopes.share`` / ``scopes.note_split``,
    ``trace.kernel_roofline`` and ``trace.idle_share``."""
    trace = Trace.read(DATA / f"{name}.scoped.trace.json")
    doc = json.loads((DATA / f"{name}.program.json").read_text())
    smap = {(n, tuple(shapes)): s for n, shapes, s in doc["scopes"]}
    note = {}
    for group in (scopes.MARGIN, scopes.KRYLOV, scopes.KERNEL):
        want = scopes.share(trace, smap, group)
        got = parsed.share(trace, smap, group, note, "scopes")
        assert got == want or got == pytest.approx(want, rel=1e-12)
    assert note["scopes"] == scopes.note_split(trace, smap)
    ctx = _ctx(trace)
    ctx.info["time_tile"] = 1
    want = tr.kernel_roofline(trace, 819e9)["share"]
    assert _read("fused_kernel_roofline.mesh2x2", ctx) == pytest.approx(want, rel=1e-12)
    want = tr.idle_share(trace)
    assert _read("device_idle_share.mesh2x2", ctx) == pytest.approx(want, rel=1e-12)


def _recorded():
    """A 0.16 s window of the cell on a 2x2 v5e mesh (four 64-step chunks,
    before ``halo_pad`` became one pad and a slab landing): the trace, and
    the scope map, ring, ``info`` and metrics the chip read from it."""
    with gzip.open(DATA / f"{CELL}.scoped.trace.json.gz", "rt") as f:
        trace = Trace.from_json(json.load(f))
    doc = json.loads((DATA / f"{CELL}.program.json").read_text())
    smap = {(n, tuple(shapes)): s for n, shapes, s in doc["scopes"]}
    return trace, doc, smap, [tuple(r) for r in doc["spans"]]


def test_bench_mesh_recorded_readers_read_the_chips_values(monkeypatch):
    """The five readers, fed the chip's trace, ring and scope map, read what
    they read on the chip; the exchange is mapped on every chip, and the
    note's split adds up to the harness's."""
    trace, doc, smap, ring = _recorded()
    monkeypatch.setattr(scopes, "scope_map", lambda: smap)
    monkeypatch.setattr(scopes, "ring", lambda: ring)
    assert len(trace.devices) == 4
    start = doc["window_start_s"]
    window = generator.Window(start, start + 1.0,
                              [generator.Unit(0.0, 0.0, True)] * doc["units"])
    ctx = cell.Context(cell={}, config={}, traffic={"chunk_steps": 64}, window=window,
                       setup_s=0.0, kind=doc["device_kind"], info=doc["info"],
                       trace=trace)
    for metric in READERS:
        assert _read(metric, ctx) == pytest.approx(doc["metrics"][metric], rel=1e-9)
    assert ctx.notes["scopes"] == scopes.note_split(trace, smap)
    assert ctx.notes["halo_exchange"].count("/device:TPU:") == 4
    assert ctx.notes["dispatch"].startswith(f"{doc['units']} wfa.engine.dispatch spans")
