"""Operations and bytes of the work, from shapes alone.

The least time the chip could take for a piece of work is the larger of
its operations over the peak rate and its bytes over the HBM bandwidth
(``peaks.json``).  A kernel's roofline share is that least time over the
kernel's device time; ``mfu`` is the model's operations (the adapter's
``model_ops``) over the window times the int8 peak.
"""
from __future__ import annotations

import re

_SHAPE = re.compile(r"\b(s8|u8|s32|f32|bf16|f16|pred|s16|u32)\[([\d,]*)\]")
_BYTES = {"s8": 1, "u8": 1, "pred": 1, "bf16": 2, "f16": 2, "s16": 2,
          "s32": 4, "u32": 4, "f32": 4}


def hlo_shapes(text: str) -> list[tuple[str, tuple[int, ...]]]:
    """``(dtype, dims)`` of every array shape in an HLO instruction's
    text, the result first and then the operands in order."""
    out = []
    for dt, dims in _SHAPE.findall(text or ""):
        out.append((dt, tuple(int(d) for d in dims.split(",") if d)))
    return out


def _size(shape) -> int:
    n = 1
    for d in shape[1]:
        n *= d
    return n * _BYTES[shape[0]]


def w8a8_call(text: str) -> tuple[float, float] | None:
    """(operations, bytes) of one ``cim_w8a8_matmul`` call from its HLO
    text: the int8 weight [K, N], the activation [M, K] (int8 or float),
    the per-channel scale and bias [N], and the result [M, N]."""
    shapes = hlo_shapes(text)
    if len(shapes) < 3:
        return None
    out, operands = shapes[0], shapes[1:]
    w = [s for s in operands if s[0] == "s8" and len(s[1]) == 2]
    if len(out[1]) != 2 or not w:
        return None
    m, n = out[1]
    w = max(w, key=lambda s: s[1][0] * s[1][1])
    k = w[1][0]
    if w[1][1] != n:
        return None
    a = next((s for s in operands if s is not w and len(s[1]) == 2
              and s[1] == (m, k)), None)
    if a is None:
        return None
    scales = sum(_size(s) for s in operands if len(s[1]) <= 2
                 and s is not w and s is not a)
    return 2.0 * m * k * n, float(_size(w) + _size(a) + _size(out) + scales)


def least_seconds(ops: float, nbytes: float, op_peak: float,
                  bw: float) -> float:
    return max(ops / op_peak, nbytes / bw)
