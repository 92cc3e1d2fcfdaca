"""Reduce a profiler trace of the measured window to device metrics.

`jax.profiler.ProfileData` reads the `.xplane.pb`. Device operations are
the events of the `XLA Ops` line of each `/device:...` plane, each named
by its HLO instruction's text (`%run.72 = f32[...] custom-call(...),
custom_call_target="tpu_custom_call"`): the reduction keeps the
instruction's name and counts a `tpu_custom_call` as a Pallas kernel. On
a host without an accelerator (the CPU test) they are the host events
that carry an `hlo_op` stat. The benchmark's own spans (`qbench.window`,
`qbench.dispatch`, `qbench.wait`, `qbench.input`) are host events on the
same clock. Everything is clipped to the window span.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

SPAN = "qbench."
Interval = Tuple[float, float]


class Summary(NamedTuple):
    window_s: float                 # length of the window span
    busy_s: float                   # union of device op intervals, per chip
    apps: int                       # applications: dispatch spans in it
    kernel_s: float                 # Pallas kernel time, per chip
    other_s: float                  # every other device op, per chip
    op_totals: List[Tuple[str, float]]      # by op name, longest first
    idle_gaps: List[Tuple[str, float]]      # labelled gaps, longest first


def load(trace_dir: str):
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(max(paths, key=os.path.getmtime))


KERNEL = 'custom_call_target="tpu_custom_call"'


def op_name(text: str) -> str:
    """`%run.72 = f32[...] custom-call(...), ...` -> `run.72`, with
    `:kernel` appended for a Pallas kernel."""
    name = text.split(" = ", 1)[0].strip().lstrip("%") if " = " in text \
        else text
    return name + ":kernel" if KERNEL in text else name


def device_ops(pd) -> Dict[str, List[Tuple[str, float, float]]]:
    """{plane: [(op name, start ns, end ns)]}."""
    out: Dict[str, List[Tuple[str, float, float]]] = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    out.setdefault(plane.name, []).extend(
                        (op_name(e.name), e.start_ns,
                         e.start_ns + e.duration_ns)
                        for e in line.events)
    if out:
        return out
    host = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    op = dict(e.stats).get("hlo_op")
                    if op is not None:
                        host.append((str(op), e.start_ns,
                                     e.start_ns + e.duration_ns))
    return {"/host:CPU": host} if host else {}


def host_spans(pd) -> List[Tuple[str, float, float]]:
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((e.name[len(SPAN):], e.start_ns,
                            e.start_ns + e.duration_ns)
                           for e in line.events if e.name.startswith(SPAN))
    return out


def union(intervals: Iterable[Interval]) -> List[Interval]:
    merged: List[Interval] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], e))
        else:
            merged.append((s, e))
    return merged


def clip(intervals: Iterable[Interval], lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of [lo, hi) around the merged busy intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def label(gap: Interval, spans) -> str:
    """The benchmark span that covers most of the gap, or `other`."""
    best, name = 0.0, "other"
    for n, s, e in spans:
        ov = _overlap(gap, (s, e))
        if ov > best and n != "window":
            best, name = ov, n
    return name


def summarize(pd, kernel_names: Optional[set] = None,
              top: int = 10) -> Summary:
    """Kernels are the ops named `...:kernel`, and those in kernel_names
    (the CPU test marks some there)."""
    spans = host_spans(pd)
    wins = [(s, e) for n, s, e in spans if n == "window"]
    if not wins:
        raise ValueError("the trace holds no qbench.window span")
    lo, hi = wins[-1]
    apps = sum(1 for n, s, e in spans
               if n == "dispatch" and lo <= s and e <= hi)
    planes = device_ops(pd)
    kernel_names = kernel_names or set()
    busy = kernel = other = 0.0
    totals: Dict[str, float] = {}
    idle: List[Tuple[str, float]] = []
    for ops in planes.values():
        clipped = [(name, *iv) for name, s, e in ops
                   for iv in clip([(s, e)], lo, hi)]
        merged = union((s, e) for _, s, e in clipped)
        busy += sum(e - s for s, e in merged)
        for name, s, e in clipped:
            totals[name] = totals.get(name, 0.0) + (e - s)
            if name.endswith(":kernel") or name in kernel_names:
                kernel += e - s
            else:
                other += e - s
        idle += [(label(g, spans), (g[1] - g[0]) * 1e-9)
                 for g in gaps(merged, lo, hi)]
    chips = max(1, len(planes))
    return Summary(
        window_s=(hi - lo) * 1e-9, busy_s=busy * 1e-9 / chips, apps=apps,
        kernel_s=kernel * 1e-9 / chips, other_s=other * 1e-9 / chips,
        op_totals=sorted(((n, t * 1e-9 / chips) for n, t in totals.items()),
                         key=lambda x: -x[1])[:top],
        idle_gaps=sorted(idle, key=lambda x: -x[1])[:top])
