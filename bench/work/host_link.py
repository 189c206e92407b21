"""Bytes that cross the host link: in one step, every spring-state leaf
that lives in pinned host memory goes to the device and comes back; in one
traced copy, the logical size of the array it moves."""
from __future__ import annotations

import math
import re

DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
               "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
               "u64": 8}
_ARRAY = re.compile(r"([a-z]+[0-9]*)\[([0-9,]*)\]")


def bytes_per_step(leaves) -> float:
    """``leaves``: arrays with ``.nbytes`` and ``.sharding.memory_kind``."""
    return 2.0 * sum(x.nbytes for x in leaves
                     if getattr(x.sharding, "memory_kind", None) == "pinned_host")


def _operand(name: str) -> str:
    return name.split(" copy-start(", 1)[1]


def from_host(name: str) -> bool:
    """A ``copy-start`` whose operand lives in host memory moves host→HBM."""
    return "S(5)" in _operand(name)


def copy_bytes(name: str) -> int:
    """Bytes a ``copy-start`` moves: its operand's element count times the
    element size, without the layout's tile padding."""
    m = _ARRAY.search(_operand(name))
    dims = [int(d) for d in m.group(2).split(",") if d]
    return math.prod(dims) * DTYPE_BYTES[m.group(1)]
