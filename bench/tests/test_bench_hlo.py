"""Instruction text from a TPU trace: opcodes, shapes, launch bytes."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.harness import hlo  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"


def _lines():
    return [hlo.parse(s) for s in (DATA / "explicit_ops.hlo.txt").read_text().splitlines()]


def test_bench_hlo_parses_every_recorded_operation():
    ins = _lines()
    assert all(i is not None for i in ins)
    assert [i.opcode for i in ins] == ["while", "custom-call", "fusion", "fusion", "broadcast"]
    assert ins[0].name == "while" and ins[0].opcode in hlo.CONTAINERS


def test_bench_hlo_kernel_launch_bytes():
    (kernel,) = [i for i in _lines() if hlo.is_stencil_kernel(i)]
    assert kernel.name == "closed_call.4"
    assert kernel.result == [("f32", (512, 512, 128))]
    assert kernel.operands == [("s32", (1, 2)), ("f32", (516, 516, 128))]
    # one result written, two operands read, each once
    assert hlo.launch_bytes(kernel) == 4 * 512 * 512 * 128 + 8 + 4 * 516 * 516 * 128


def test_bench_hlo_shapes_ignore_layouts():
    assert hlo.shapes("f32[2,512,128]{2,1,0:T(8,128)S(1)}") == [("f32", (2, 512, 128))]
    assert hlo.nbytes(hlo.shapes("(s32[]{:T(128)}, bf16[4,4]{1,0})")) == 4 + 32


def test_bench_hlo_non_instructions_and_reduction_kernels():
    assert hlo.parse("bench.window") is None
    # a reduction kernel writes scalars: not the stencil kernel
    red = hlo.parse('%c.1 = f32[1,2]{1,0} custom-call(f32[64,128]{1,0} %a, '
                    'f32[64,128]{1,0} %b), custom_call_target="tpu_custom_call"')
    assert red is not None and not hlo.is_stencil_kernel(red)
