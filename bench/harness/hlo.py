"""HLO instruction text: opcode, shapes and the bytes one launch moves.

A TPU profiler trace names each device operation by its instruction as the
compiled program prints it, operands with their shapes::

    %closed_call.4 = f32[512,512,128]{2,1,0:T(8,128)} custom-call(
        s32[1,2]{1,0:T(1,128)S(1)} %broadcast.2,
        f32[516,516,128]{2,1,0:T(8,128)} %fusion.6),
        custom_call_target="tpu_custom_call", ...

so the bytes of a launch come from its own shapes: each operand read once
and each result written once, whatever tiling produced them.
"""
from __future__ import annotations

import re
from typing import List, NamedTuple, Optional, Tuple

DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}
_SHAPE = re.compile(r"\b(" + "|".join(DTYPE_BYTES) + r")\[([0-9,]*)\]")
_HEAD = re.compile(r"^\s*(?:ROOT\s+)?%(\S+)\s+=\s+")
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")

#: control flow around other operations; their device time is their body's
CONTAINERS = frozenset({"while", "conditional", "call"})


class Instruction(NamedTuple):
    name: str
    opcode: str
    result: List[Tuple[str, Tuple[int, ...]]]
    operands: List[Tuple[str, Tuple[int, ...]]]
    attrs: str


def shapes(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    return [(dt, tuple(int(d) for d in dims.split(",") if d))
            for dt, dims in _SHAPE.findall(text)]


def nbytes(shape_list) -> int:
    total = 0
    for dt, dims in shape_list:
        n = DTYPE_BYTES[dt]
        for d in dims:
            n *= d
        total += n
    return total


def _close(text: str, i: int) -> int:
    """Index of the parenthesis closing the one opened just before ``i``."""
    depth = 1
    while i < len(text):
        if text[i] == "(":
            depth += 1
        elif text[i] == ")":
            depth -= 1
            if depth == 0:
                return i
        i += 1
    return len(text)


def parse(text: str) -> Optional[Instruction]:
    """One instruction line; ``None`` for text that is not one."""
    head = _HEAD.match(text)
    if head is None:
        return None
    rest = text[head.end():]
    # the result shape may be a tuple, with layouts such as ``T(8,128)``
    # inside; the opcode is the first lower-case word after a space that
    # opens a parenthesis
    m = _OPCODE.search(rest)
    if m is None:
        return None
    close = _close(rest, m.end())
    return Instruction(
        name=head.group(1),
        opcode=m.group(1),
        result=shapes(rest[: m.start()]),
        operands=shapes(rest[m.end():close]),
        attrs=rest[close + 1:],
    )


def is_stencil_kernel(ins: Instruction) -> bool:
    """A Mosaic kernel launch that writes a field: the fused stencil kernel
    (the reductions' kernels write scalars)."""
    return (ins.opcode == "custom-call"
            and 'custom_call_target="tpu_custom_call"' in ins.attrs
            and any(len(dims) >= 3 for _, dims in ins.result))


def launch_bytes(ins: Instruction) -> int:
    """Operands read once plus results written once."""
    return nbytes(ins.operands) + nbytes(ins.result)
