"""Lightweight counter/histogram registry (DESIGN.md §12).

The runtime scoreboard the serving layer inherits: plans compiled,
plan-cache hits, overflow escalations, contract audits — anything a
long-lived process wants to report without attaching a profiler. The
resilience layer (DESIGN.md §13) reports here under `resilience.*`:
`ladder_attempts` / `ladder_escalations` / `ladder_exhausted` (checked
operator ladders), `kernel_fallbacks` (+ `.{site}`) for pallas→XLA arm
fallbacks, `plan_degradations` (executor degrade-once),
`serve_shed` / `serve_retries` / `serve_evictions` /
`serve_deadline_evictions` (serving), `degradations` and `faults_fired`
(fault injection). The relational query server (DESIGN.md §14) reports
under `qserve.*`: `submitted` / `completed` / `shed` / `rejected` /
`deadline_evictions` / `failed` (request outcomes), `plans_compiled` /
`plan_cache_hits` (signature cache), `fast_runs` / `fast_failures` /
`safe_runs` / `safe_escalations` / `saturations` (execution paths), and
`breaker_opens` / `breaker_probes` / `breaker_closes` (circuit
breakers). The memory governor (DESIGN.md §15) adds the `qserve.bytes_*`
and oom families: `qserve.bytes_reserved` (histogram — in-flight bytes
ticket ledger observed every tick; its max must never exceed the
budget), `qserve.mem_rejections` (never-fits typed rejections),
`qserve.mem_deferrals` (fits-later deferrals — also `serve.mem_deferrals`
for the batched engine's slot governor), `qserve.chunked_runs`
(server-dispatched morsel runs), `engine.morsel_runs` (individual
morsels executed by the out-of-core driver), and
`resilience.oom_injected` (deterministic `oom:<site>` faults fired).
Metrics
are plain Python (no jax import, no locks beyond the GIL's atomicity for
`+=` on ints): incrementing a counter costs one dict lookup + an add, so
instrumented hot paths stay hot.

Usage::

    from repro.obs import metrics

    metrics.counter("engine.plans_compiled").inc()
    metrics.histogram("engine.run_wall_s").observe(dt)
    with metrics.span("qserve.pad"):   # wall seconds -> histogram("qserve.pad")
        ...
    metrics.snapshot()   # {name: value | summary-dict}, for reporting

Spans (`span`) time one piece of host work where it happens. Each adds
its wall seconds to the histogram of its name and opens a
`jax.profiler.TraceAnnotation` of that name, so under a profiler the span
lands on the host plane of the same trace as the device's operations, on
their clock. The served path's spans: `qserve.signature` (the plan
signature's hash per submission), `qserve.pad` (eager padding of a run's
inputs), `qserve.dispatch` (handing a run to the executor),
`qserve.count_sync` (the host waiting on the result count),
`plan.stats` (each catalog statistic computed rather than looked up),
`plan.audit` (a signature's peak-bytes audit and morsel probing),
`exec.compile` (lowering and compiling a served executable, or loading it
from the persistent cache) and `exec.run` (calling it; its trace event
carries the executable's scope map, see `engine.executor.ServedProgram`).
"""
from __future__ import annotations

import contextlib
import dataclasses
import time


@dataclasses.dataclass
class Counter:
    """Monotone event count."""

    name: str
    value: int = 0

    def inc(self, n: int = 1) -> None:
        self.value += n

    def as_value(self):
        return self.value


# Percentiles need retained observations; cap the buffer so a long-lived
# server's histograms stay O(1) memory. At the cap, every other retained
# sample is dropped and the keep-stride doubles — a deterministic (no RNG)
# systematic sample that stays uniformly spread over the whole stream.
SAMPLE_CAP = 4096


def percentiles(values, pcts=(50, 95, 99)) -> dict:
    """Nearest-rank percentiles over raw values: ``{"p50": ..., ...}``.
    Shared by Histogram.summary() and anything holding its own latency
    list (BENCH writers); benches should stop hand-rolling medians."""
    out = {}
    s = sorted(float(v) for v in values)
    for p in pcts:
        key = f"p{p:g}"
        if not s:
            out[key] = 0.0
            continue
        rank = max(int(-(-len(s) * p // 100)), 1)  # ceil, 1-based
        out[key] = s[min(rank, len(s)) - 1]
    return out


@dataclasses.dataclass
class Histogram:
    """Streaming summary of an observed quantity (count/sum/min/max/last)
    plus a bounded sample buffer for percentile export.

    No buckets: the consumers here (CLI tables, BENCH_*.json rows) want
    moments and a few percentiles, and a full histogram would force a
    bucket-boundary choice on every metric. `mean` is derived; percentiles
    are nearest-rank over the retained samples (exact until SAMPLE_CAP
    observations, a deterministic stride-thinned approximation after)."""

    name: str
    count: int = 0
    total: float = 0.0
    min: float = float("inf")
    max: float = float("-inf")
    last: float = 0.0
    samples: list = dataclasses.field(default_factory=list, repr=False)
    stride: int = 1  # keep every stride-th observation (doubles at the cap)

    def observe(self, x: float) -> None:
        x = float(x)
        self.count += 1
        self.total += x
        self.min = x if x < self.min else self.min
        self.max = x if x > self.max else self.max
        self.last = x
        if (self.count - 1) % self.stride == 0:
            self.samples.append(x)
            if len(self.samples) >= SAMPLE_CAP:
                self.samples = self.samples[::2]
                self.stride *= 2

    @property
    def mean(self) -> float:
        return self.total / self.count if self.count else 0.0

    def percentile(self, p: float) -> float:
        return percentiles(self.samples, (p,))[f"p{p:g}"]

    def summary(self, pcts=(50, 95, 99)) -> dict:
        """Moments + percentiles, JSON-ready — the BENCH_serve.json /
        ServeEngine latency-report shape."""
        out = {"count": self.count, "mean": self.mean,
               "min": self.min if self.count else 0.0,
               "max": self.max if self.count else 0.0}
        out.update(percentiles(self.samples, pcts))
        return out

    def as_value(self):
        if not self.count:
            return {"count": 0}
        return {"count": self.count, "sum": self.total, "mean": self.mean,
                "min": self.min, "max": self.max, "last": self.last}


class MetricsRegistry:
    """Name -> metric map. `counter()`/`histogram()` get-or-create, so call
    sites never coordinate registration; asking for an existing name with
    the other kind raises (one name, one type)."""

    def __init__(self):
        self._metrics: dict[str, object] = {}

    def _get(self, name: str, kind):
        m = self._metrics.get(name)
        if m is None:
            m = self._metrics[name] = kind(name)
        elif not isinstance(m, kind):
            raise TypeError(
                f"metric {name!r} already registered as "
                f"{type(m).__name__}, not {kind.__name__}")
        return m

    def counter(self, name: str) -> Counter:
        return self._get(name, Counter)

    def histogram(self, name: str) -> Histogram:
        return self._get(name, Histogram)

    def snapshot(self) -> dict:
        return {name: m.as_value() for name, m in sorted(self._metrics.items())}

    def reset(self) -> None:
        self._metrics.clear()


REGISTRY = MetricsRegistry()


def counter(name: str) -> Counter:
    return REGISTRY.counter(name)


def histogram(name: str) -> Histogram:
    return REGISTRY.histogram(name)


@contextlib.contextmanager
def span(name: str, **metadata):
    """Time the enclosed host work into `histogram(name)` and mark it as a
    `jax.profiler.TraceAnnotation` (with `metadata` as the event's stats;
    they are formatted only while a profiler records). Without a profiler
    a span costs a `perf_counter` pair, an inactive TraceMe and an observe:
    a few microseconds."""
    from jax.profiler import TraceAnnotation  # lazy: this module imports no jax

    t0 = time.perf_counter()
    try:
        with TraceAnnotation(name, **metadata):
            yield
    finally:
        REGISTRY.histogram(name).observe(time.perf_counter() - t0)


def snapshot() -> dict:
    return REGISTRY.snapshot()


def reset() -> None:
    REGISTRY.reset()
