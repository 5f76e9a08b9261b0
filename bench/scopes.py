"""Reading a profiler trace by the program's own names: device time by the
round's named scopes, host time by the engine's ``fl.*`` spans, and idle
time put down to the innermost ``fl.*`` or ``bench.*`` span open on the
driving thread at each instant.

The planes are :mod:`trace_reduce`'s plain data, except that a device
event may carry a fourth element: the operation's named-scope path, the
HLO ``op_name`` metadata (``jit(chunk)/while/body/closed_call/local_phase/
jit(phase)/candidate_report/top_k``). A TPU trace's op events name the HLO
instruction (``%fusion.12 = ...``) but carry no ``op_name``, so
:func:`load_xplane` maps (module, instruction) to it through the compiled
programs' HLO text, kept as ``*.hlo.txt`` files beside the trace
(``FederatedEngine.program_texts``). :func:`plain` drops the path again for
:func:`trace_reduce.reduce`. A trace of a program without the scopes, the
spans or the texts reads as all unscoped, with no ``fl.*`` span: nothing
here raises for it.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

import trace_reduce as tr

# the round's phases, in program order, then the eval program
PHASES = ("local_phase", "candidate_report", "selection", "aggregation",
          "global_update")
SCOPES = PHASES + ("eval",)
SPAN_PREFIXES = ("bench.", "fl.")
MODULES_LINE = "XLA Modules"
_HLO_MODULE = re.compile(r"^HloModule\s+([^\s,]+)")
_HLO_OP = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=.*?'
                     r'\bmetadata=\{op_name="([^"]*)"')
_EVENT_OP = re.compile(r"^%?([^\s=]+)")
# "vmap(candidate_report)", "transpose(jvp(local_phase))": a transform or
# a nested jit wrapped around a path component
_WRAPPED = re.compile(r"^[^()/]+\((.*)\)$")


def op_paths(hlo_texts) -> dict:
    """(module, instruction) -> op_name path, from compiled HLO texts."""
    out: dict = {}
    for text in hlo_texts:
        m = _HLO_MODULE.match(text)
        module = m.group(1) if m else ""
        for line in text.splitlines():
            op = _HLO_OP.match(line)
            if op:
                out.setdefault((module, op.group(1)), op.group(2))
    return out


def _module_at(modules: list, starts: list, t: float) -> str:
    """The module (``jit_chunk`` of ``jit_chunk(5312...)``) whose run on
    the device holds time ``t``, of the start-ordered ``modules``; ""
    where none does."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t < modules[i][1] + modules[i][2]:
        return modules[i][0].split("(", 1)[0]
    return ""


def scope_path(ev: list, modules: list, starts: list, paths: dict):
    """A device event's op_name, from the compiled text's metadata of its
    instruction in the module running at its start; None where the texts
    do not hold it."""
    op = _EVENT_OP.match(ev[0])
    if not op:
        return None
    return paths.get((_module_at(modules, starts, ev[1]), op.group(1)))


def load_xplane(trace_dir: str) -> list:
    """Planes of the newest ``.xplane.pb`` under ``trace_dir``; device
    events carry their scope path where :func:`scope_path` finds one,
    with the ``*.hlo.txt`` files under ``trace_dir``."""
    from jax._src.lib import _profile_data

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    texts = []
    for t in sorted(glob.glob(os.path.join(trace_dir, "**", "*.hlo.txt"),
                              recursive=True)):
        with open(t) as f:
            texts.append(f.read())
    by_op = op_paths(texts)
    planes = []
    for p in _profile_data.ProfileData.from_file(paths[-1]).planes:
        device = tr.DEVICE_PLANE.match(p.name) is not None
        lines = [(ln.name, list(ln.events)) for ln in p.lines]
        modules = sorted(([e.name, e.start_ns, e.duration_ns]
                          for name, evs in lines if name == MODULES_LINE
                          for e in evs), key=lambda m: m[1])
        starts = [m[1] for m in modules]
        out = []
        for name, evs in lines:
            events = []
            for e in evs:
                ev = [e.name, e.start_ns, e.duration_ns]
                if device and name == tr.OPS_LINE and by_op:
                    path = scope_path(ev, modules, starts, by_op)
                    if path is not None:
                        ev.append(path)
                events.append(ev)
            out.append({"name": name, "events": events})
        planes.append({"name": p.name, "lines": out})
    return planes


def plain(planes: list) -> list:
    """The planes with every event cut to (name, start_ns, duration_ns)."""
    return [{"name": p["name"],
             "lines": [{"name": ln["name"],
                        "events": [ev[:3] for ev in ln["events"]]}
                       for ln in p["lines"]]}
            for p in planes]


def _unwrap(component: str) -> str:
    m = _WRAPPED.match(component)
    while m:
        component = m.group(1)
        m = _WRAPPED.match(component)
    return component


def innermost_scope(path: str, scopes=SCOPES) -> str:
    """The last component of a scope path that names one of ``scopes``,
    bare or wrapped in transforms; "" where none does."""
    for comp in reversed(path.split("/")):
        name = _unwrap(comp)
        if name in scopes:
            return name
    return ""


def host_spans(planes: list) -> list:
    """The ``bench.*`` and ``fl.*`` host spans as (name, start_ns, end_ns,
    line), in start order; ``line`` tells the threads apart."""
    return sorted(((ev[0], ev[1], ev[1] + ev[2], (i, j))
                   for i, p in enumerate(planes)
                   if p["name"].startswith("/host:")
                   for j, ln in enumerate(p["lines"]) for ev in ln["events"]
                   if ev[0].startswith(SPAN_PREFIXES)),
                  key=lambda sp: sp[1])


def _idle_pieces(spans: list, gaps: list) -> list:
    """Each gap cut where a span opens or closes, each piece given to the
    innermost (shortest) of ``spans`` open over all of it: (gap, [(label,
    ns), ...]). One sweep over the start-ordered spans: a gap only looks
    at those open around it."""
    out, live, i = [], [], 0
    for s, e in gaps:
        while i < len(spans) and spans[i][1] < e:
            live.append(spans[i])
            i += 1
        live = [sp for sp in live if sp[2] > s]
        cuts = sorted({s, e} | {t for sp in live for t in sp[1:3]
                                if s < t < e})
        pieces = []
        for a, b in zip(cuts, cuts[1:]):
            around = [sp for sp in live if sp[1] <= a and sp[2] >= b]
            lab = (min(around, key=lambda sp: sp[2] - sp[1])[0]
                   if around else "no host span")
            pieces.append((lab, b - a))
        out.append(((s, e), pieces))
    return out


def reduce(planes: list, window_span: str, top: int = 10) -> dict | None:
    """Inside the host span named ``window_span``: device seconds by
    innermost scope (containers left out, "" for unscoped ops) and the
    ``top`` operations of each scope by device seconds, count and
    seconds of each ``fl.*`` span on any thread, and the device's idle
    time by the innermost ``fl.*``/``bench.*`` span open at each instant
    on the thread that holds the window span. The ``top`` longest gaps
    carry the label that holds most of each. None where the trace holds no
    device plane or no such span."""
    devices = [[ev for ln in p["lines"] if ln["name"] == tr.OPS_LINE
                for ev in ln["events"]]
               for p in planes if tr.DEVICE_PLANE.match(p["name"])]
    spans = host_spans(planes)
    bounds = [sp for sp in spans if sp[0] == window_span]
    if not devices or not bounds:
        return None
    lo, hi = min(sp[1] for sp in bounds), max(sp[2] for sp in bounds)
    driver = bounds[0][3]
    n_dev = len(devices)
    scope_ns: dict = {}
    by_op: dict = {}
    gaps = []
    for ops in devices:
        for ev in ops:
            cut = min(ev[1] + ev[2], hi) - max(ev[1], lo)
            name = tr.op_name(ev[0])
            if cut > 0 and name not in tr.CONTAINERS:
                key = innermost_scope(ev[3]) if len(ev) > 3 else ""
                scope_ns[key] = scope_ns.get(key, 0.0) + cut
                by_op[key, name] = by_op.get((key, name), 0.0) + cut
        busy = tr.union([(ev[1], ev[1] + ev[2]) for ev in ops], lo, hi)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    gaps.sort()
    pieces = _idle_pieces([sp for sp in spans if sp[3] == driver], gaps)
    by_label: dict = {}
    longest = []
    for (s, e), parts in pieces:
        held: dict = {}
        for lab, ns in parts:
            by_label[lab] = by_label.get(lab, 0.0) + ns / n_dev
            held[lab] = held.get(lab, 0.0) + ns
        longest.append([max(held, key=held.get), (e - s) * 1e-9])
    longest.sort(key=lambda g: -g[1])
    span_s: dict = {}
    for name, s, e, _ in spans:
        cut = min(e, hi) - max(s, lo)
        if name.startswith("fl.") and cut > 0:
            c = span_s.setdefault(name, {"count": 0, "s": 0.0})
            c["count"] += 1
            c["s"] += cut * 1e-9
    scope_ops: dict = {}
    for (key, name), ns in sorted(by_op.items(), key=lambda kv: -kv[1]):
        if len(scope_ops.setdefault(key, [])) < top:
            scope_ops[key].append([name, ns / n_dev * 1e-9])
    return {
        "window_s": (hi - lo) * 1e-9,
        "scope_s": {k: v / n_dev * 1e-9 for k, v in scope_ns.items()},
        "scope_ops": scope_ops,
        "span_s": span_s,
        "idle_gaps": longest[:top],
        "idle_by_span": {k: v * 1e-9 for k, v in by_label.items()},
    }


def phase_cover(scope_s: dict) -> float | None:
    """Share of the device time outside ``eval`` that the round's five
    phase scopes hold; None where nothing ran."""
    rest = sum(scope_s.values()) - scope_s.get("eval", 0.0)
    if rest <= 0:
        return None
    return sum(scope_s.get(p, 0.0) for p in PHASES) / rest
