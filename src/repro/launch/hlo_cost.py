"""Cost figures read off a compiled XLA module: the compiler's own cost
analysis and the bytes its collectives move. No import side effects, so
tests and benchmarks can use it without the dry-run's device setup."""
from __future__ import annotations

import re

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

def _shape_bytes(shape_str: str) -> int:
    """'f32[16,32]' or tuple '(f32[4], bf16[2,3])' -> total bytes."""
    total = 0
    for m in re.finditer(r"(\w+)\[([\d,]*)\]", shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Sum output-shape bytes of every collective op in the HLO (per-device
    program => per-device bytes), by op kind."""
    out = {"all-reduce": 0, "all-gather": 0, "reduce-scatter": 0,
           "all-to-all": 0, "collective-permute": 0}
    for line in hlo_text.splitlines():
        s = line.strip()
        m = re.match(r"^[%\w][\w.\-]*\s*=\s*(.*?)\s*"
                     r"(all-reduce|all-gather|reduce-scatter|all-to-all|"
                     r"collective-permute)(-start)?\(", s)
        if not m:
            continue
        out[m.group(2)] += _shape_bytes(m.group(1))
    return out


def cost_dict(compiled) -> dict:
    """The compiled module's cost analysis (flops, bytes accessed, ...)."""
    return compiled.cost_analysis()
