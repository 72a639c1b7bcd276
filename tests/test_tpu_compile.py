"""Compile-only tests: the main path's kernels through Mosaic for a v5e.

Nothing here runs on a chip.  Each test compiles a kernel (or the jitted
step around it) for a described, unattached ``v5e:2x2`` topology at the
paper's industrial size, so a kernel Mosaic refuses — a misaligned block,
an op with no TPU lowering, a window past the VMEM limit — fails here
instead of on the chip.  Every compile passes ``interpret=False`` itself.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.compiler import auto_tile, lower_group
from repro.compiler.codegen import compile_group, compile_group_sharded
from repro.configs.heat3d import HeatConfig
from repro.core.jaxcompat import shard_map
from repro.core.program import _group_ops

HEAT = HeatConfig()
SHAPE = (HEAT.nx, HEAT.ny, HEAT.nz)    # 512 x 512 x 128, 3.3e7 cells


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but never read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _heat_ops(shape):
    """The Fig. 3 heat body recorded on ``shape`` (the service's heat3d)."""
    from repro.service.workloads import _record_heat3d

    program, _ = _record_heat3d(shape, np.float32, 40000)
    (loop, ops), = [g for g in _group_ops(program) if g[0] is not None]
    return loop, ops


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _auto_k():
    loop, ops = _heat_ops(SHAPE)
    group = lower_group(ops)
    fields = {"T": (HEAT.nz, np.float32)}
    return auto_tile(group, SHAPE[:2], loop.n, fields=fields)


@pytest.mark.parametrize("k", ["1", "auto"])
def test_heat_fused_kernel_compiles(one_chip, k):
    k = 1 if k == "1" else _auto_k()
    assert k >= 1
    _, ops = _heat_ops(SHAPE)
    step = compile_group(ops, {"T": SHAPE}, {"T": np.float32},
                         interpret=False, time_tile=k)
    x = jax.ShapeDtypeStruct(SHAPE, jnp.float32, sharding=one_chip)
    _compile(step, {"T": x})


def test_auto_tile_fits_vmem_at_industrial_size():
    """At 512x512x128 the static rule stops below k=8: the k=8 window
    leaves too small an X block (or none) inside the kernel's VMEM."""
    from repro.compiler.ir import fused_x_block

    k = _auto_k()
    loop, ops = _heat_ops(SHAPE)
    group = lower_group(ops)
    fields = {"T": (HEAT.nz, np.float32)}
    bxb = fused_x_block(group, k, SHAPE[:2], fields)
    assert 1 < k < 8 and bxb >= 4 * k * group.halo


def test_heat_resident_kernel_compiles(one_chip):
    _, ops = _heat_ops(SHAPE)
    k = 2
    margin = k  # the halo-resident layout's run-wide margin, k·h
    step = compile_group(ops, {"T": SHAPE}, {"T": np.float32},
                         interpret=False, time_tile=k, resident=margin)
    padded = (SHAPE[0] + 2 * margin, SHAPE[1] + 2 * margin, SHAPE[2])
    x = jax.ShapeDtypeStruct(padded, jnp.float32, sharding=one_chip)
    _compile(step, {"T": x})


def test_heat_resident_runner_double_buffers(one_chip, monkeypatch):
    """The single-device resident runner at 512x512x128 as the engine
    builds it for Mosaic (64 steps, auto tile): its kernel aliases no
    operand it reads, and its step loop holds no copy of a resident-extent
    array (launches run in pairs, so the two buffers trade places)."""
    import repro.kernels.ops as kops
    from repro.engine import RunOptions, plan
    from repro.engine.executor import _trace_plan
    from repro.service.workloads import _record_heat3d

    monkeypatch.setattr(kops, "_interpret", lambda: False)
    program, _ = _record_heat3d(SHAPE, np.float32, 64)
    p = plan(program, RunOptions(backend="pallas"))
    K = p.layout.pad
    assert K > 0 and p.segments[0].kind == "fused"
    env = {n: jax.ShapeDtypeStruct(f.shape, f.dtype, sharding=one_chip)
           for n, f in program.fields.items()}
    text = jax.jit(lambda e: _trace_plan(p, e), donate_argnums=0).lower(
        env).compile().as_text()
    kernels = [l for l in text.splitlines() if "tpu_custom_call" in l
               and "custom-call(" in l]
    assert kernels and all("output_to_operand_aliasing" not in l
                           for l in kernels), kernels
    resident = f"f32[{SHAPE[0] + 2 * K},{SHAPE[1] + 2 * K},{SHAPE[2]}]"
    assert " while(" in text
    copies = [l for l in text.splitlines()
              if re.search(r"= \S+ copy(-start)?\(", l) and resident in l]
    assert not copies, copies


def test_heat_sharded_step_compiles(topo):
    """One fused step inside shard_map on a 2x2 mesh: 256x256x128 bricks,
    halo exchange by ppermute."""
    _, ops = _heat_ops(SHAPE)
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    step = compile_group_sharded(ops, {"T": SHAPE}, {"T": np.float32},
                                 mesh_xy=(2, 2), axis_names=("data", "model"),
                                 interpret=False)
    spec = PartitionSpec("data", "model", None)
    mapped = shard_map(step, mesh=mesh, in_specs=({"T": spec},),
                       out_specs={"T": spec})
    x = jax.ShapeDtypeStruct(SHAPE, jnp.float32,
                             sharding=NamedSharding(mesh, spec))
    text = _compile(mapped, {"T": x}).as_text()
    assert "collective-permute" in text


def test_dual_dot_fuses_one_sweep(one_chip):
    """The solvers' reduction pair is one multi-output XLA fusion that
    reads the shared operand once (see repro.kernels.ops.dual_dot)."""
    from repro.kernels import ops as kops

    x = jax.ShapeDtypeStruct(SHAPE, jnp.float32, sharding=one_chip)
    text = jax.jit(lambda r, z: kops.dual_dot(r, z, r, r)).lower(
        x, x).compile().as_text()
    # fusions that read the full-size operands: one, yielding both sums
    sweeps = re.findall(r"= (\([^=]*\)|\S+) fusion\((%r[^,]*, %z[^)]*)\)",
                        text)
    assert len(sweeps) == 1, sweeps
    assert sweeps[0][0].count("f32[]") == 2, sweeps


def test_mg_smoother_compiles(one_chip):
    """The damped-Jacobi smoother at the finest level of a 129^3 Poisson."""
    from repro.compiler import mg_fine_operator
    from repro.solver.api import _split
    from repro.solver.multigrid import JACOBI_OMEGA, _record_smoother
    from repro.solver.presets import poisson_program

    n = 129
    program = poisson_program((n, n, n))
    (_, op_ops), _ = _split(program, "T")
    fine = mg_fine_operator(lower_group(op_ops), "T", (n, n, n))
    ops, shapes, dtypes = _record_smoother(fine, JACOBI_OMEGA, np.float32)
    step = compile_group(ops, shapes, dtypes, interpret=False)
    x = jax.ShapeDtypeStruct((n, n, n), jnp.float32, sharding=one_chip)
    _compile(step, {"x": x, "b": x})


def test_heat_mesh_runner_compiles_at_the_deployment_size(topo, monkeypatch):
    """The ``heat3d.mesh2x2`` runner as the engine builds it for Mosaic:
    1024x1024x128 over the 2x2 mesh (512x512x128 bricks), 64 steps, auto
    tile, on the halo-resident layout.  As on one chip, its kernel aliases
    no operand it reads and its loop copies no resident-extent array (the
    two buffers trade places); no brick is repacked (no ``wfa.halo.pad``);
    the collective-permutes carry the exchange's scope; every whole-array
    operation is named; and it fits a chip."""
    import repro.kernels.ops as kops
    from repro.engine import RunOptions, plan, sharded_runner
    from repro.service.workloads import _record_heat3d

    monkeypatch.setattr(kops, "_interpret", lambda: False)
    shape = (2 * SHAPE[0], 2 * SHAPE[1], SHAPE[2])
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("x", "y"))
    program, _ = _record_heat3d(shape, np.float32, 64)
    p = plan(program, RunOptions(backend="pallas", mesh=mesh))
    K = p.layout.pad
    assert K > 0 and p.segments[0].kind == "fused"
    run, sharding = sharded_runner(p)
    env = {n: jax.ShapeDtypeStruct(f.shape, f.dtype, sharding=sharding)
           for n, f in program.fields.items()}
    compiled = run.lower(env).compile()
    text = compiled.as_text()
    kernels = [l for l in text.splitlines() if "tpu_custom_call" in l
               and "custom-call(" in l]
    assert kernels and all("output_to_operand_aliasing" not in l
                           for l in kernels), kernels
    resident = f"f32[{SHAPE[0] + 2 * K},{SHAPE[1] + 2 * K},{SHAPE[2]}]"
    assert " while(" in text
    copies = [l for l in text.splitlines()
              if re.search(r"= \S+ copy(-start)?\(", l) and resident in l]
    assert not copies, copies
    assert "wfa.halo.pad" not in text
    permutes = [l for l in text.splitlines()
                if re.search(r"\bcollective-permute(-start)?\(", l)]
    assert permutes and all("wfa.halo.exchange" in l for l in permutes)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 2**30
    assert _unscoped_whole_arrays(text) == []


def _unscoped_whole_arrays(text):
    """Operations a device trace shows (those outside fused computations)
    that write 2**20 elements or more and carry no ``wfa.*`` scope: a
    trace could not name their time."""
    from repro.engine.stats import instruction_key

    skip = {"parameter", "get-tuple-element", "tuple", "bitcast", "constant",
            "while", "conditional", "call"}
    fused, out = False, []
    for line in text.splitlines():
        if line and not line[0].isspace():
            fused = "fused" in line.split(" ")[0]
            continue
        key = instruction_key(line)
        if fused or key is None:
            continue
        opcode = re.search(r"\s([a-z][a-z0-9\-]*)\(", line.split("=", 1)[1]).group(1)
        sizes = [np.prod([int(d) for d in s[s.index("[") + 1:-1].split(",") if d])
                 for s in key[1]]
        if (opcode not in skip and max(sizes, default=0) >= 2**20
                and not re.search(r'op_name="[^"]*wfa\.', line)):
            out.append(key)
    return out
