"""``correct`` on ``heat3d.mesh2x2`` at a small size on four virtual CPU
devices: true for the program, false for the lower-precision control and
for each fault of the timed path, and the bytes the exchange sends.

The cases run in one child process (the main test process keeps one
device) at a 32x32x8 global grid, 16x16x8 bricks on the 2x2 mesh."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]

CHILD = r'''
import json, sys
from pathlib import Path
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
import jax, jax.numpy as jnp
from bench.harness import manifest
from bench.harness.small import run_small, small_root
from repro.core import halo
from repro.engine import executor, reset_stats, stats

CELL = "heat3d.mesh2x2"
root = small_root(Path(sys.argv[2]))
path = root / "bench/configs/heat3d_ftcs_mesh2x2.json"
config = dict(json.loads(path.read_text()), grid=[32, 32, 8])
path.write_text(json.dumps(config))
out = {}


def run(case, system="program"):
    r = run_small(root, CELL, system=system)
    out[case] = {"correct": r["correct"], "checks": r["checks"],
                 "attempted": r["attempted"], "failed": r["failed"]}


run("program")
run("control", system="control")
trace_plan, shift = executor._trace_plan, halo._ppermute_shift


def altered(plan, env):
    env = trace_plan(plan, env)
    return {k: v.at[tuple(n // 2 for n in v.shape)].add(1.0) for k, v in env.items()}


for case, patch in [
        ("state_unchanged", (executor, "_trace_plan", lambda plan, env: dict(env))),
        ("answer_altered", (executor, "_trace_plan", altered)),
        ("exchange_left_out", (halo, "_ppermute_shift",
                               lambda x, *a: jnp.zeros_like(x)))]:
    module, name, fn = patch
    setattr(module, name, fn)
    try:
        run(case)
    finally:
        executor._trace_plan, halo._ppermute_shift = trace_plan, shift

doc = manifest.load(root)
scheme = manifest.scheme(config, root)
traffic = manifest.traffic(manifest.cell(doc, CELL)["traffic"], root)
stepper = scheme.stepper(config, traffic, jax.devices()[:4], False)
(x,) = scheme.inputs(config, 7, 1)
reset_stats()
jax.block_until_ready(stepper.run(x))
out["halo"] = {"info": stepper.info, "halo_bytes": stats.halo_bytes,
               "launches": stats.launches, "steps": stepper.steps}
print("RESULT " + json.dumps(out))
'''


@pytest.fixture(scope="module")
def cases(tmp_path_factory):
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "-c", CHILD, str(ROOT),
                        str(tmp_path_factory.mktemp("bench_mesh"))],
                       capture_output=True, text=True, timeout=600, env=env)
    assert p.returncode == 0, p.stderr[-4000:]
    (line,) = [s for s in p.stdout.splitlines() if s.startswith("RESULT ")]
    return json.loads(line[len("RESULT "):])


def test_bench_mesh_program_is_correct(cases):
    r = cases["program"]
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0


@pytest.mark.parametrize("case", ["control", "state_unchanged", "answer_altered",
                                  "exchange_left_out"])
def test_bench_mesh_fault_is_not_correct(cases, case):
    """The bfloat16 control, a runner that leaves the state as it was, one
    altered cell, and bricks whose exchange delivers zeros in place of
    their neighbours' margins."""
    r = cases[case]
    assert not r["correct"], (case, r["checks"])


def test_bench_mesh_halo_bytes_are_the_slabs_sent(cases):
    """Each launch a chip of the 2x2 mesh sends one X slab (k·h, by, nz)
    and one Y slab (bx + 2k·h, k·h, nz) of float32: the counter moves by
    that times the chunk's launches, and the scheme's ``info`` says it."""
    h = cases["halo"]
    info = h["info"]
    k = info["time_tile"]
    bx, by, nz = info["brick"]
    assert (info["mesh"], info["brick"]) == ([2, 2], [16, 16, 8])
    assert info["segment"] == "fused"
    per_launch = 4 * nz * k * (by + bx + 2 * k)
    assert info["halo_bytes_per_launch"] == per_launch
    launches = h["steps"] // k + h["steps"] % k
    assert h["launches"] == launches
    assert h["halo_bytes"] == launches * per_launch
