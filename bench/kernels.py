"""Least work of the kernels whose roofline share the benchmark reports.

Counted from what the operation is given and must return, read off the
operand and result shapes of its call in the trace, never from the
implementation's own traffic or compares: a later kernel that computes
the same operation differently is judged on the same yardstick.
"""

from __future__ import annotations

import json
import os
import re
from typing import List, Tuple

_SHAPE = re.compile(r"\b(pred|s8|u8|s16|u16|s32|u32|s64|u64|bf16|f16|f32|f64)\[([0-9,]*)\]")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
          "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8}
PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; an unknown kind is
    an error, never a default."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS}")
    return table[device_kind]


def _elements(dims: str) -> int:
    n = 1
    for d in filter(None, dims.split(",")):
        n *= int(d)
    return n


def call_shapes(hlo_text: str) -> Tuple[List[Tuple[str, int]], List[Tuple[str, int]]]:
    """``(results, operands)`` of an HLO call as ``(dtype, elements)``."""
    head, sep, tail = hlo_text.partition("custom-call(")
    if not sep:
        raise ValueError(f"not a custom call: {hlo_text[:80]!r}")
    depth, end = 1, len(tail)
    for i, ch in enumerate(tail):           # layouts nest parentheses: T(8,128)
        depth += (ch == "(") - (ch == ")")
        if depth == 0:
            end = i
            break
    results = [(t, _elements(d)) for t, d in _SHAPE.findall(head.partition("=")[2])]
    operands = [(t, _elements(d)) for t, d in _SHAPE.findall(tail[:end])]
    return results, operands


def member_probe_bytes(hlo_text: str) -> int:
    """Least HBM bytes of one membership probe: read the query pairs and
    the table pairs once, write one boolean per query.

    The call takes four int32 planes (query high and low words, table
    high and low words) and returns one plane per query.
    """
    results, operands = call_shapes(hlo_text)
    if len(operands) != 4 or len(results) != 1:
        raise ValueError(f"member_probe takes 4 operands and gives 1 result, "
                         f"not {len(operands)} and {len(results)}")
    n_q = operands[0][1]
    n_t = operands[2][1]
    return (_BYTES[operands[0][0]] * n_q + _BYTES[operands[1][0]] * n_q
            + _BYTES[operands[2][0]] * n_t + _BYTES[operands[3][0]] * n_t
            + n_q)
