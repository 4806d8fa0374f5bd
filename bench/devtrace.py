"""Reduction of a profiler trace to device busy time, idle gaps and the
operations that took the most time.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData``.  Per TPU plane, the ``XLA Ops`` line holds
every operation the device ran and the ``XLA Modules`` line every program;
host planes hold the benchmark's spans (``bench.*``, written with
``TraceAnnotation``) on the same clock.  The window is the host span
``bench.window``.

- busy: the union of the operations' intervals inside the window;
- gaps: the idle stretches between them, each labelled with the benchmark
  span that covers most of it on the host, or with ``OUTSIDE`` where
  most of it lies outside every benchmark span (the trainer's own code:
  its Runtime scheduling the step tasks, the wait on the loss);
- program gaps: idle time between consecutive runs of one program;
- top operations: device self time (an operation's time less that of the
  operations nested in it, as a loop's body is in the loop) summed by
  operation, named by its HLO instruction and result type.
"""

from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

DEVICE_PLANE = re.compile(r"/device:TPU:\d+$")
WINDOW = "bench.window"
OUTSIDE = "outside benchmark spans"


def find_xplane(trace_dir) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(path))


def _events(line):
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


@dataclass
class Trace:
    """The parts of a trace the reduction reads, as plain lists of
    (name, start_ns, end_ns)."""
    window: tuple
    devices: dict = field(default_factory=dict)   # plane -> {"ops", "modules"}
    host_spans: list = field(default_factory=list)


def extract(pd, span_prefix: str = "bench.") -> Trace:
    host, devices = [], {}
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {ln.name: _events(ln) for ln in plane.lines}
            devices[plane.name] = {"ops": lines.get("XLA Ops", []),
                                   "modules": lines.get("XLA Modules", [])}
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host += [e for e in _events(ln) if e[0].startswith(span_prefix)]
    windows = [e for e in host if e[0] == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"{len(windows)} {WINDOW} spans in the trace")
    _, w0, w1 = windows[0]
    return Trace(window=(w0, w1), devices=devices,
                 host_spans=[e for e in host if e[0] != WINDOW])


def clip(events, w0, w1):
    return [(n, max(a, w0), min(b, w1)) for n, a, b in events
            if b > w0 and a < w1]


def union(intervals) -> list:
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def covered(g0, g1, spans) -> float:
    return sum(b - a for a, b in union((max(a, g0), min(b, g1))
                                       for _, a, b in spans
                                       if b > g0 and a < g1))


def label_gap(g0, g1, spans) -> str:
    by_name = defaultdict(list)
    for s in spans:
        by_name[s[0]].append(s)
    best, name = (g1 - g0) - covered(g0, g1, spans), OUTSIDE
    for n, ss in sorted(by_name.items()):
        c = covered(g0, g1, ss)
        if c > best:
            best, name = c, n
    return name


_LAYOUT = re.compile(r"\{[^{}]*\}")


def op_label(hlo: str) -> str:
    """'%fusion.12 = bf16[8,4096]{1,0:T(8,128)} fusion(...), ...' ->
    'fusion.12 bf16[8,4096]': the instruction and its result type."""
    name, _, rest = hlo.partition(" = ")
    rest = _LAYOUT.sub("", rest)
    if rest.startswith("("):
        depth = 0
        for i, c in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(c, 0)
            if depth == 0:
                break
        rtype = rest[:i + 1]
    else:
        rtype = rest.split(" ")[0]
    return f"{name.lstrip('%')} {rtype}"[:160]


def self_times(ops) -> dict:
    """Device self time per operation label: a nested operation's time
    is taken from the one it runs in."""
    out = defaultdict(float)
    labels = {}
    stack = []                                  # [end, label]
    for n, a, b in sorted(ops, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= a:
            stack.pop()
        label = labels.get(n) or labels.setdefault(n, op_label(n))
        out[label] += b - a
        if stack:
            out[stack[-1][1]] -= b - a
        stack.append([b, label])
    return out


@dataclass
class Reduction:
    window_s: float
    busy_s: float            # mean over the device planes
    gaps: list               # [(label, seconds)], longest first, device 0
    program_gaps_s: dict     # program name -> idle seconds between its runs
    program_runs: dict       # program name -> runs inside the window
    top_ops: list            # [(name, seconds)], most first, device 0


def reduce(tr: Trace) -> Reduction:
    w0, w1 = tr.window
    if not tr.devices:
        raise ValueError("the trace has no TPU plane")
    busy, first = [], None
    for name in sorted(tr.devices):
        ops = clip(tr.devices[name]["ops"], w0, w1)
        u = union((a, b) for _, a, b in ops)
        busy.append(sum(b - a for a, b in u))
        if first is None:
            first = (name, ops, u)
    _, ops, u = first
    edges = [w0] + [x for iv in u for x in iv] + [w1]
    gaps = [(label_gap(a, b, tr.host_spans), (b - a) * 1e-9)
            for a, b in zip(edges[::2], edges[1::2]) if b > a]
    gaps.sort(key=lambda g: -g[1])

    runs = defaultdict(list)
    for n, a, b in clip(tr.devices[first[0]]["modules"], w0, w1):
        runs[re.sub(r"\(\d+\)$", "", n)].append((a, b))
    program_gaps = {n: sum(max(0.0, r[i + 1][0] - r[i][1])
                           for i in range(len(r) - 1)) * 1e-9
                    for n, r in ((n, sorted(r)) for n, r in runs.items())}

    top = sorted(((n, t * 1e-9) for n, t in self_times(ops).items()),
                 key=lambda kv: -kv[1])

    return Reduction(window_s=(w1 - w0) * 1e-9,
                     busy_s=sum(busy) / len(busy) * 1e-9,
                     gaps=gaps, program_gaps_s=program_gaps,
                     program_runs={n: len(r) for n, r in runs.items()},
                     top_ops=top)
