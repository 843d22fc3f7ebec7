"""What the program reports about itself, read for per-layer metrics.

Two sources, both written by the program under test and absent from a
program that predates them (the metrics' readers then return None):

* Its span registry (`repro.obs.metrics`): each `metrics.span(name)` adds
  its wall seconds to the histogram `name`. The harness runs one cell in
  one process, so after the window the registry holds the whole run:
  set-up's planning and compiling, and every query's host work in order.
* Its traced executable. A traced run's `.xplane.pb` holds the device's
  operations ("XLA Ops", named by their HLO instruction) and, on the host
  plane, the `exec.run` span that handed the served executable to the
  device, whose metadata gives the executable's HLO module name and its
  map from instruction to scope path (`"<path> <instr> <instr>|<path> ..."`,
  the node scope and the phase scope the instruction's `op_name` carries,
  e.g. `join.phj/probe`).

`innermost` gives each busy instant of the window to the innermost
operation running then (the one that started last, so a `while` loop's
time goes to the operations of its body while they run), so the seconds of
the four phases and of "unscoped" work sum to the window's busy time.
An operation counts as scoped only inside an execution of the traced
module (its events on the "XLA Modules" line); eager operations between
executions (padding, the result count) count as unscoped. `idle_by_span`
names the rest of the window: each instant no operation ran goes to the
innermost host span open then, the program's (`qserve.*`, `plan.*`,
`exec.*`) or the harness's (`bench.*`).
"""
from __future__ import annotations

import functools
import glob
import heapq
import os

import tracing

PHASES = ("partition", "probe", "materialize", "aggregate")
RUN_SPAN = "exec.run"
PROGRAM_SPANS = ("qserve.", "plan.", "exec.")
MODULES_LINE = "XLA Modules"


# ---------------------------------------------------------------------------
# the span registry
# ---------------------------------------------------------------------------
def _histogram(name: str):
    """The program's histogram `name`, or None where it recorded none."""
    from repro.obs import metrics

    if not metrics.snapshot().get(name, {}).get("count"):
        return None
    return metrics.histogram(name)


def span_total_s(name: str) -> float | None:
    """Seconds the run spent in span `name`, over all its occurrences."""
    h = _histogram(name)
    return None if h is None else h.total


def window_mean_s(names, queries: int) -> float | None:
    """Seconds per window query in the spans `names` together: the last
    `queries` occurrences of each (the window's, after set-up's). None
    where a span is missing, occurred fewer times, or its histogram
    thinned its samples."""
    if queries < 1:
        return None
    total = 0.0
    for name in names:
        h = _histogram(name)
        if h is None or h.stride != 1 or len(h.samples) < queries:
            return None
        total += sum(h.samples[-queries:])
    return total / queries


# ---------------------------------------------------------------------------
# the traced executable
# ---------------------------------------------------------------------------
def instruction(op_name: str) -> str:
    """The HLO instruction of an op as the profiler names it
    (`%fusion.33 = s32[...] fusion(...)` -> `fusion.33`)."""
    return op_name.split(" = ", 1)[0].lstrip("%")


def decode_scopes(text: str) -> dict[str, str]:
    """{instruction: scope path} from an `exec.run` span's `scopes`."""
    out = {}
    for group in filter(None, text.split("|")):
        path, *names = group.split(" ")
        out.update(dict.fromkeys(names, path))
    return out


def phase_of(path: str | None) -> str:
    """The innermost phase scope of a scope path, or "unscoped"."""
    for seg in reversed((path or "").split("/")):
        if seg in PHASES:
            return seg
    return "unscoped"


def load(trace_dir: str) -> dict:
    """From the newest trace under `trace_dir`: the device's ops, the host
    spans (the harness's and the program's), the traced executables' scope
    maps by module, and the modules' executions, each (name, start, end) in
    seconds on the trace's clock."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    path = max(paths, key=os.path.getmtime)
    return _load(path, os.path.getmtime(path))


@functools.lru_cache(maxsize=2)  # each metric's reader loads the same trace
def _load(path: str, mtime: float) -> dict:
    from jax.profiler import ProfileData

    ops, spans, modules, runs = [], [], [], []
    for plane in ProfileData.from_file(path).planes:
        on_device = plane.name.startswith(tracing.DEVICE_PLANE)
        for line in plane.lines:
            if on_device and line.name not in (tracing.OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                start = ev.start_ns * 1e-9
                item = (ev.name, start, start + ev.duration_ns * 1e-9)
                if on_device:
                    (ops if line.name == tracing.OPS_LINE else modules).append(item)
                elif ev.name.startswith(tracing.SPAN_PREFIX):
                    spans.append(item)
                elif ev.name.startswith(PROGRAM_SPANS):
                    spans.append(item)
                    stats = {k: str(v) for k, v in ev.stats}
                    if ev.name == RUN_SPAN and stats.get("scopes"):
                        runs.append((start, stats.get("program", ""),
                                     decode_scopes(stats["scopes"])))
    runs.sort(key=lambda run: run[0])  # the latest map of a module wins
    programs = {module: scopes for _, module, scopes in runs}
    return {"ops": ops, "spans": spans, "programs": programs,
            "modules": [(name.split("(", 1)[0], s, e) for name, s, e in modules]}


def innermost(ops, lo: float, hi: float) -> list[tuple[tuple, float]]:
    """[(op, seconds), ...]: each instant of [lo, hi] in which some op ran,
    given to the op that started last among those running (the shorter on
    a tie). The seconds sum to `tracing.busy(ops, lo, hi)`."""
    clipped = ((max(op[1], lo), min(op[2], hi), op) for op in ops)
    events = sorted((s, e, op) for s, e, op in clipped if e > s)
    bounds = sorted({t for s, e, _ in events for t in (s, e)})
    out: dict[tuple, float] = {}
    heap: list = []
    i = 0
    for t0, t1 in zip(bounds, bounds[1:]):
        while i < len(events) and events[i][0] <= t0:
            s, e, op = events[i]
            heapq.heappush(heap, (-s, e, i, op))
            i += 1
        while heap and heap[0][1] <= t0:
            heapq.heappop(heap)
        if heap:
            op = heap[0][3]
            out[op] = out.get(op, 0.0) + (t1 - t0)
    return list(out.items())


def scope_seconds(trace: dict, lo: float, hi: float) -> dict[str, float]:
    """{scope path: device seconds} over [lo, hi] by the innermost-op rule;
    "" for operations under no scope or outside the traced module."""
    programs = trace["programs"]
    runs = sorted((s, e, name) for name, s, e in trace["modules"]
                  if name in programs)
    out: dict[str, float] = {}
    for (name, start, _), secs in innermost(trace["ops"], lo, hi):
        module = next((m for s, e, m in runs if s <= start < e), None)
        path = programs[module].get(instruction(name), "") if module else ""
        out[path] = out.get(path, 0.0) + secs
    return out


def phase_seconds(trace: dict, lo: float, hi: float) -> dict[str, float]:
    """{phase: device seconds} over [lo, hi], "unscoped" included."""
    out = dict.fromkeys(PHASES + ("unscoped",), 0.0)
    for path, secs in scope_seconds(trace, lo, hi).items():
        out[phase_of(path)] += secs
    return out


def idle_by_span(trace: dict, lo: float, hi: float) -> dict[str, float]:
    """{span: idle seconds} over [lo, hi]: each instant in which no op ran,
    given to the innermost (shortest) host span open then, "none" when no
    span is. The seconds sum to the window less `tracing.busy`."""
    busy = tracing.merged([(s, e) for _, s, e in trace["ops"]], lo, hi)
    gaps, t = [], lo
    for s, e in busy + [(hi, hi)]:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    out: dict[str, float] = {}
    for g0, g1 in gaps:
        cuts = sorted({g0, g1} | {c for _, s, e in trace["spans"]
                                  for c in (s, e) if g0 < c < g1})
        for t0, t1 in zip(cuts, cuts[1:]):
            open_ = [sp for sp in trace["spans"] if sp[1] <= t0 and t1 <= sp[2]]
            name = min(open_, key=lambda sp: sp[2] - sp[1])[0] if open_ else "none"
            out[name] = out.get(name, 0.0) + (t1 - t0)
    return out


def traced_phase_s(record: dict, phase: str) -> float | None:
    """Device seconds per traced query in `phase` ("unscoped" for work
    under no phase scope), from the harness's traced window. None without
    a trace, without device ops, or without the executable's scope map."""
    import run  # for TRACE_DIR (run as a script, the harness is __main__)

    if not record.get("trace"):
        return None
    trace = load(str(run.TRACE_DIR))
    if not trace["ops"] or not trace["programs"]:
        return None
    lo, hi = tracing.window(trace["spans"], "bench.window")
    return phase_seconds(trace, lo, hi)[phase] / record["trace"]["queries"]
