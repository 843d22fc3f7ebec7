"""Reduction of a profiler trace to the benchmark's device numbers.

`load` reads the `.xplane.pb` that `jax.profiler.trace` writes and returns
the device's operations and the host spans, on one clock, in seconds:

  ops    (name, start, end) of every event on the "XLA Ops" line of each
         TPU plane, the operations as the profiler names them (the whole
         HLO instruction; `short_name` keeps its head for the breakdown)
  spans  (name, start, end) of every host event whose name starts with
         "bench." (the harness's `TraceAnnotation`s)

The rest is plain arithmetic over those lists: `busy` is the length of the
union of the ops' intervals inside the window, `top_ops` the operations
that took most time in all, and `idle_gaps` the longest stretches of the
window in which no op ran, each named by the innermost harness span open
at its middle.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


def load(trace_dir: str) -> tuple[list, list]:
    """(ops, spans) from the newest trace under `trace_dir`."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    ops, spans = [], []
    for plane in data.planes:
        on_device = plane.name.startswith(DEVICE_PLANE)
        for line in plane.lines:
            if on_device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                start = ev.start_ns * 1e-9
                item = (ev.name, start, start + ev.duration_ns * 1e-9)
                if on_device:
                    ops.append(item)
                elif ev.name.startswith(SPAN_PREFIX):
                    spans.append(item)
    return ops, spans


_HLO = re.compile(r"^(%[\w.-]+) = (\(.*?\)|\S+) ([a-z][\w-]*)\(")


def short_name(name: str) -> str:
    """The head of an op's name as the profiler prints it (the whole HLO
    instruction): instruction, op and result shape, without layouts."""
    m = _HLO.match(name)
    if not m:
        return name
    shape = "(tuple)" if m[2].startswith("(") else re.sub(r"\{[^}]*\}", "", m[2])
    return f"{m[1]} {m[3]} {shape}"


def merged(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The union of (start, end) intervals clipped to [lo, hi], as sorted
    disjoint intervals."""
    out: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy(ops, lo: float, hi: float) -> float:
    """Seconds of [lo, hi] in which some op ran."""
    return sum(e - s for s, e in merged([(s, e) for _, s, e in ops], lo, hi))


def top_ops(ops, lo: float, hi: float, n: int = 10) -> list:
    """[[name, seconds], ...]: the n ops with the most time inside
    [lo, hi], summed over their events, longest first."""
    total: dict[str, float] = {}
    for name, s, e in ops:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            total[name] = total.get(name, 0.0) + d
    top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[short_name(k), v] for k, v in top]


def idle_gaps(ops, spans, lo: float, hi: float, n: int = 10) -> list:
    """[[label, seconds], ...]: the n longest idle stretches of [lo, hi],
    each labelled by the innermost span open at its middle ("none" when
    no span is)."""
    gaps, t = [], lo
    for s, e in merged([(s, e) for _, s, e in ops], lo, hi) + [(hi, hi)]:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) / 2
        open_ = [sp for sp in spans if sp[1] <= mid < sp[2]]
        label = min(open_, key=lambda sp: sp[2] - sp[1])[0] if open_ else "none"
        out.append([label, e - s])
    return out


def window(spans, name: str) -> tuple[float, float]:
    """(start, end) of the one span called `name`."""
    found = [(s, e) for nm, s, e in spans if nm == name]
    if len(found) != 1:
        raise ValueError(f"expected one {name!r} span in the trace, found {len(found)}")
    return found[0]
