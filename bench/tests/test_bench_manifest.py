"""The manifest, discovery by name and the contract's shape of
``BENCHMARK.json``; the entry point's refusal to run without a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.harness import cell, device, generator, manifest  # noqa: E402
from bench.harness.small import run_small, small_root  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def doc():
    return manifest.load()


def test_bench_manifest_names_and_keys(doc):
    assert set(doc) == {"command", "paths", "run_seconds", "configs", "workloads",
                        "end_to_end", "per_layer"}
    names = [e["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for e in doc[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in doc["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and (ROOT / c["file"]).is_file()
    for w in doc["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in doc["end_to_end"] + doc["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in doc["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in doc["end_to_end"]}
    for m in doc["per_layer"]:
        assert m["moves"] in e2e


@pytest.mark.parametrize("traced", [False, True])
def test_bench_manifest_every_cell_reports_and_finds_its_files(doc, traced):
    e2e = {m["name"]: m for m in doc["end_to_end"]}
    for w in doc["workloads"]:
        manifest.config(doc, w["config"])
        manifest.traffic(w["traffic"])
        got = manifest.metrics(doc, w["name"], traced)
        names = {m["name"] for m in got}
        if not traced:
            assert "setup_s" in names and len(names) >= 2
        else:
            assert names, w["name"]
            for m in got:  # a per-layer metric goes with what it moves
                assert w["name"] in e2e[m["moves"]].get("workloads", [w["name"]])
        for m in got:
            assert callable(manifest.reader(m["name"]))


def test_bench_manifest_four_chip_cells_within_half(doc):
    four = [w for w in doc["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(doc["workloads"]) // 2)


def test_bench_manifest_unknown_names_fail(doc):
    with pytest.raises(manifest.ManifestError):
        manifest.cell(doc, "no.such.cell")
    with pytest.raises(manifest.ManifestError):
        manifest.traffic("no_such_mix")
    with pytest.raises(manifest.ManifestError):
        manifest.reader("no_such_metric")
    with pytest.raises(manifest.ManifestError):
        manifest.scheme({"name": "x", "scheme": "no_such_scheme"})
    with pytest.raises(manifest.ManifestError):
        manifest.scheme({"name": "x"})
    with pytest.raises(manifest.ManifestError):
        manifest.loop({"loop": "no_such_loop"})


def test_bench_loop_refuses_a_scheme_that_lacks_what_it_needs(doc):
    """The explicit scheme cannot be solved: the solving loop says so
    before it builds anything."""
    config = manifest.config(doc, "heat3d_ftcs_512")
    traffic = manifest.traffic("solves4")
    with pytest.raises(generator.SchemeError, match="solver"):
        manifest.loop(traffic)(config, traffic, 1, manifest.scheme(config), False, [])


def test_bench_peaks_lookup():
    p = device.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops_per_s"] == 197e12
    assert "TPU v5e" in p["source"]
    with pytest.raises(KeyError):
        device.peaks("cpu")


def test_bench_new_cell_needs_only_new_files(tmp_path):
    """A configuration, a traffic mix, a per-layer metric and a cell added
    as new files plus appended manifest entries, with no file edited."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    doc = manifest.load()
    (tmp_path / "bench/configs/heat3d_ftcs_256.json").write_text(json.dumps(
        dict(manifest.config(doc, "heat3d_ftcs_512"), name="heat3d_ftcs_256",
             grid=[16, 16, 8])))
    (tmp_path / "bench/traffic/chunks8.json").write_text(json.dumps(
        dict(manifest.traffic("chunks64"), chunk_steps=8)))
    (tmp_path / "bench/metrics/chunks_done.py").write_text(
        "def read(ctx):\n    return float(len(ctx.window.done))\n")
    doc["configs"].append({"name": "heat3d_ftcs_256", "source": "https://arxiv.org/abs/2209.13768",
                           "file": "bench/configs/heat3d_ftcs_256.json", "reduced": [],
                           "why": "a test"})
    doc["workloads"].append({"name": "heat3d.small", "config": "heat3d_ftcs_256",
                             "traffic": "chunks8", "chips": 1, "why": "a test"})
    for m in doc["end_to_end"]:
        if m["name"] == "cell_updates_per_s":
            m["workloads"].append("heat3d.small")
    doc["per_layer"].append({"name": "chunks_done", "unit": "chunks", "better": "higher",
                             "source": "program_counter", "layer": "engine plan/executor (engine/)",
                             "moves": "cell_updates_per_s", "workloads": ["heat3d.small"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))

    new = manifest.load(tmp_path)
    assert manifest.config(new, "heat3d_ftcs_256", tmp_path)["grid"] == [16, 16, 8]
    assert [m["name"] for m in manifest.metrics(new, "heat3d.small", True)] == ["chunks_done"]
    lines = []
    r = cell.run("heat3d.small", 5, 0.2, False, t_process=0.0, root=tmp_path,
                 require_tpu=False, cache=False, log=lines.append)
    assert r["correct"] and set(r["metrics"]) == {"setup_s", "cell_updates_per_s"}
    ctx = cell.Context(cell={}, config={}, traffic={}, window=generator.Window(
        0.0, 1.0, [generator.Unit(0.0, 1.0, True)]), setup_s=1.0, kind="cpu", info={})
    assert manifest.reader("chunks_done", tmp_path)(ctx) == 1.0


#: a scheme that no file of the benchmark knows: diffusion along z alone
NEW_SCHEME = '''
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import fields
from bench.harness.generator import LOWER, Stepper, max_rel_err


@partial(jax.jit, static_argnames=("steps", "dtype"))
def reference(T, c, steps, dtype):
    T = T.astype(dtype)
    mask = fields.interior(T.shape)
    cc = jnp.asarray(c, T.dtype)

    def step(_, T):
        return jnp.where(mask, T + cc * (jnp.roll(T, 1, 2) + jnp.roll(T, -1, 2) - 2 * T), T)

    return jax.lax.fori_loop(0, steps, step, T).astype(jnp.float32)


def inputs(config, seed, count):
    return fields.plates(seed, config["grid"], count, config["plate"], config["dtype"])


def compare_steps(config, inp, out, steps):
    ref = reference(inp, float(config["omega"]), steps, config["dtype"])
    return {"max_rel_err": max_rel_err(out, ref)}


def stepper(config, traffic, devices, control):
    steps, c = int(traffic["chunk_steps"]), float(config["omega"])
    if control:
        return Stepper(lambda T: reference(T, c, steps, LOWER[config["dtype"]]), steps, {})
    from repro.core.field import Field
    from repro.core.program import ForLoop, scoped_program
    from repro.engine import plan, single_runner
    from repro.engine.options import RunOptions

    with scoped_program() as program:
        T = Field("T", shape=tuple(config["grid"]), dtype=np.dtype(config["dtype"]))
        with ForLoop("time_loop", steps):
            T[1:-1, 0, 0] = (1 - 2 * c) * T[1:-1, 0, 0] + c * (T[2:, 0, 0] + T[:-2, 0, 0])
    run = single_runner(plan(program, RunOptions(backend="pallas")))
    return Stepper(lambda T: run({"T": T})["T"], steps, {})
'''


def test_bench_new_scheme_needs_only_new_files(tmp_path):
    """A scheme the harness has never seen, added as one file with its
    configuration and cell: it runs, is correct, and its control is not."""
    small_root(tmp_path)
    before = {p: p.read_bytes() for p in tmp_path.rglob("*") if p.is_file()}
    (tmp_path / "bench/schemes/diffz.py").write_text(NEW_SCHEME)
    doc = manifest.load(tmp_path)
    cfg = dict(manifest.config(doc, "heat3d_ftcs_512", tmp_path), name="diffz_16",
               scheme="diffz", omega=0.2)
    (tmp_path / "bench/configs/diffz_16.json").write_text(json.dumps(cfg))
    doc["configs"].append({"name": "diffz_16", "source": "-", "reduced": [],
                           "file": "bench/configs/diffz_16.json", "why": "a test"})
    doc["workloads"].append({"name": "diffz.small", "config": "diffz_16",
                             "traffic": "chunks64", "chips": 1, "why": "a test"})
    for m in doc["end_to_end"]:
        if m["name"] == "cell_updates_per_s":
            m["workloads"].append("diffz.small")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    changed = [p for p, b in before.items()
               if p.name != "BENCHMARK.json" and p.read_bytes() != b]
    assert changed == []

    program = run_small(tmp_path, "diffz.small")
    assert program["correct"], program["checks"]
    assert set(program["metrics"]) == {"setup_s", "cell_updates_per_s"}
    control = run_small(tmp_path, "diffz.small", system="control")
    assert not control["correct"], control["checks"]


def _run_entry(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "heat3d.explicit",
         "--seed", "2147483699", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_bench_no_tpu_exits_nonzero_with_no_result():
    p = _run_entry(ROOT)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


def test_bench_bare_checkout_exits_nonzero(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    p = _run_entry(tmp_path)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
