"""Reduction of a profiler trace to device busy time, kernel time and idle
gaps labelled by the benchmark's own host spans.

A trace is handled as plain data: a list of planes, each
``{"name", "lines": [{"name", "events": [[name, start_ns, duration_ns],
...]}]}``. :func:`load_xplane` builds it from the ``.xplane.pb`` that
``jax.profiler.trace`` writes; tests build it by hand or from a recorded
file. Device planes are named ``/device:<KIND>:<n>``; their ``XLA Ops``
line holds one event per operation the device ran. Host spans are the
events whose name starts with ``SPAN_PREFIX`` on any host plane.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
# XLA Ops events on a TPU are named by their HLO text, "%name.N = ..."
_HLO_NAME = re.compile(r"^%?([^\s=]+?)(?:\.\d+)?(?:\s*=|$)")
# control flow that holds other operations: busy, but not itself a cost
CONTAINERS = {"while", "conditional", "call"}


def load_xplane(trace_dir: str) -> list:
    """Planes of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax._src.lib import _profile_data

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = _profile_data.ProfileData.from_file(paths[-1])
    return [{"name": p.name,
             "lines": [{"name": ln.name,
                        "events": [[e.name, e.start_ns, e.duration_ns]
                                   for e in ln.events]}
                       for ln in p.lines]}
            for p in data.planes]


def op_name(name: str) -> str:
    """An operation's HLO instruction name without the numeric suffix XLA
    gives copies: "%maghist_batch.3 = s32[...] custom-call(...)" and
    "maghist_batch.3" both give "maghist_batch"."""
    m = _HLO_NAME.match(name)
    return m.group(1) if m else name


def device_ops(planes: list) -> list:
    """Per device plane, its operations as (name, start_ns, end_ns)."""
    out = []
    for p in planes:
        if not DEVICE_PLANE.match(p["name"]):
            continue
        ops = [(n, s, s + d) for ln in p["lines"] if ln["name"] == OPS_LINE
               for n, s, d in ln["events"]]
        out.append(ops)
    return out


def host_spans(planes: list) -> list:
    """The benchmark's host spans as (name, start_ns, end_ns)."""
    return [(n, s, s + d) for p in planes if p["name"].startswith("/host:")
            for ln in p["lines"] for n, s, d in ln["events"]
            if n.startswith(SPAN_PREFIX)]


def union(intervals: list, lo: float, hi: float) -> list:
    """Disjoint sorted intervals covering the parts of ``intervals`` that
    lie inside [lo, hi]."""
    merged: list = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def label_gap(spans: list, s: float, e: float) -> str:
    """The innermost host span around most of a gap: of the spans that
    overlap it, the one that covers most of it, the shortest on a tie."""
    best, key = "no host span", (0.0, 0.0)
    for name, hs, he in spans:
        cover = min(he, e) - max(hs, s)
        if cover > 0 and (cover, -(he - hs)) > key:
            best, key = name, (cover, -(he - hs))
    return best


def reduce(planes: list, window_span: str, top: int = 10) -> dict | None:
    """Device busy time, kernel time by operation name and labelled idle
    gaps inside the host span named ``window_span`` (the first and last
    such span bound the window). None where the trace holds no device
    plane or no such span."""
    devices = device_ops(planes)
    spans = host_spans(planes)
    bounds = [(s, e) for n, s, e in spans if n == window_span]
    if not devices or not bounds:
        return None
    lo, hi = min(s for s, _ in bounds), max(e for _, e in bounds)
    window_ns = hi - lo
    busy_ns, op_ns = [], {}
    gaps = []
    for ops in devices:
        busy = union([(s, e) for _, s, e in ops], lo, hi)
        busy_ns.append(sum(e - s for s, e in busy))
        for name, s, e in ops:
            cut = min(e, hi) - max(s, lo)
            if cut > 0:
                key = op_name(name)
                op_ns[key] = op_ns.get(key, 0.0) + cut
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n_dev = len(devices)
    by_label: dict = {}
    for s, e in gaps:
        lab = label_gap(spans, s, e)
        by_label[lab] = by_label.get(lab, 0.0) + (e - s) / n_dev
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "window_s": window_ns * 1e-9,
        "busy_s": sum(busy_ns) / n_dev * 1e-9,
        "devices": n_dev,
        "op_s": {k: v / n_dev * 1e-9 for k, v in op_ns.items()},
        "device_ops": sorted(([k, v / n_dev * 1e-9] for k, v in op_ns.items()
                              if k not in CONTAINERS),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": [[label_gap(spans, s, e), (e - s) * 1e-9]
                      for s, e in longest],
        "idle_by_span": {k: v * 1e-9 for k, v in by_label.items()},
    }
