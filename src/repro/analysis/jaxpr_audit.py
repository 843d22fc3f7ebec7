"""Static auditor over jaxprs: primitive budgets, liveness watermarks,
dtype-contract checks.

The paper's cost models price plans in *primitive* terms — number of sort
passes, partition passes, gathers/scatters — so the only way to know that
the plan XLA compiled is the plan the model priced is to count those
primitives in the traced jaxpr (DESIGN.md §11). This module is the
counting layer: a recursive walker that descends into every sub-jaxpr a
higher-order primitive carries (`pjit`, `cond` branches, `scan`/`while`
bodies, `pallas_call` kernel bodies, custom_vjp/jvp call jaxprs) and
produces:

  * a `PrimitiveBudget` — counts of the plan-shaping primitives (sorts,
    gathers, scatters, scatter-adds, all_to_alls, pallas_calls);
  * a liveness-based peak-live-bytes watermark — walking eqns in order,
    tracking each value's last use, the high-water mark of live bytes is
    an upper bound on the compiled program's residency and the witness
    for "this fusion never materializes the join output";
  * a dtype-contract report — eqns whose outputs silently widen to a
    64-bit dtype none of their inputs carried (the classic f64/i64
    promotion that doubles every downstream pass).

Counting convention: a primitive inside `scan`/`while` counts ONCE (the
static shape of the program, mirroring how the cost model prices it), not
once per iteration — trip counts are a runtime property, budgets are a
compile-time property.
"""
from __future__ import annotations

import dataclasses

import jax
from jax.extend import core as jcore
import numpy as np

SORT_PRIMS = frozenset({"sort"})
GATHER_PRIMS = frozenset({"gather"})
SCATTER_SET_PRIMS = frozenset({"scatter"})
SCATTER_COMBINE_PRIMS = frozenset(
    {"scatter-add", "scatter-mul", "scatter-min", "scatter-max"})
ALL_TO_ALL_PRIMS = frozenset({"all_to_all"})
PALLAS_PRIMS = frozenset({"pallas_call"})
WIDE_BYTES = 8  # itemsize threshold for the 64-bit promotion check


@dataclasses.dataclass(frozen=True)
class PrimitiveBudget:
    """Counts of the plan-shaping primitives in a (recursively walked)
    jaxpr. Addition/subtraction compose budgets across plan subtrees."""
    sorts: int = 0
    gathers: int = 0
    scatters: int = 0
    scatter_adds: int = 0
    float_scatter_adds: int = 0
    all_to_alls: int = 0
    pallas_calls: int = 0

    def __add__(self, other: "PrimitiveBudget") -> "PrimitiveBudget":
        return PrimitiveBudget(*(a + b for a, b in
                                 zip(self.astuple(), other.astuple())))

    def __sub__(self, other: "PrimitiveBudget") -> "PrimitiveBudget":
        return PrimitiveBudget(*(a - b for a, b in
                                 zip(self.astuple(), other.astuple())))

    def astuple(self) -> tuple:
        return dataclasses.astuple(self)

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)

    def describe(self) -> str:
        return " ".join(f"{k}={v}" for k, v in self.as_dict().items() if v)


@dataclasses.dataclass(frozen=True)
class AuditReport:
    """Everything the contract layer needs to judge one traced program."""
    budget: PrimitiveBudget
    peak_live_bytes: int
    peak_live_at: str  # primitive name at the watermark ('<args>' if inputs)
    arg_bytes: int  # bytes of the jaxpr's invars + constvars
    out_bytes: int  # bytes of the jaxpr's outvars
    promotions: tuple  # eqn descriptions that widened to 64-bit silently

    def as_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["budget"] = self.budget.as_dict()
        d["promotions"] = list(self.promotions)
        return d


# ---------------------------------------------------------------------------
# recursive walk
# ---------------------------------------------------------------------------
def _as_jaxpr(obj):
    """Normalize Jaxpr/ClosedJaxpr to the raw Jaxpr, else None."""
    if hasattr(obj, "eqns") and hasattr(obj, "invars"):
        return obj
    inner = getattr(obj, "jaxpr", None)
    if inner is not None and hasattr(inner, "eqns"):
        return inner
    return None


def subjaxprs(eqn) -> list:
    """Every sub-jaxpr an eqn's params carry (pjit/cond/scan/while bodies,
    pallas_call kernels, custom_*_call jaxprs), as raw Jaxprs."""
    out = []
    for val in eqn.params.values():
        items = val if isinstance(val, (tuple, list)) else (val,)
        for item in items:
            j = _as_jaxpr(item)
            if j is not None:
                out.append(j)
    return out


def walk_eqns(jaxpr):
    """Yield every eqn of `jaxpr` and (recursively) of its sub-jaxprs."""
    jaxpr = _as_jaxpr(jaxpr)
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in subjaxprs(eqn):
            yield from walk_eqns(sub)


def _is_float(aval) -> bool:
    dt = getattr(aval, "dtype", None)
    return dt is not None and np.issubdtype(dt, np.floating)


def budget_of_jaxpr(jaxpr) -> PrimitiveBudget:
    counts = dict.fromkeys(
        ("sorts", "gathers", "scatters", "scatter_adds",
         "float_scatter_adds", "all_to_alls", "pallas_calls"), 0)
    for eqn in walk_eqns(jaxpr):
        name = eqn.primitive.name
        if name in SORT_PRIMS:
            counts["sorts"] += 1
        elif name in GATHER_PRIMS:
            counts["gathers"] += 1
        elif name in SCATTER_SET_PRIMS:
            counts["scatters"] += 1
        elif name in SCATTER_COMBINE_PRIMS:
            counts["scatter_adds"] += 1
            if any(_is_float(v.aval) for v in eqn.outvars):
                counts["float_scatter_adds"] += 1
        elif name in ALL_TO_ALL_PRIMS:
            counts["all_to_alls"] += 1
        elif name in PALLAS_PRIMS:
            counts["pallas_calls"] += 1
    return PrimitiveBudget(**counts)


# ---------------------------------------------------------------------------
# dtype contract: no silent 64-bit promotion
# ---------------------------------------------------------------------------
def _itemsize(aval) -> int:
    dt = getattr(aval, "dtype", None)
    if dt is None or jax.dtypes.issubdtype(dt, jax.dtypes.extended):
        return 0  # no array, or a kernel's DMA semaphore: no bytes moved
    return np.dtype(dt).itemsize


def find_promotions(jaxpr) -> tuple:
    """Eqns whose outputs are 64-bit-wide while no input was: the silent
    f64/i64 promotions that double the byte volume of every later pass.
    Deliberate widenings (a 64-bit input somewhere in the eqn) are fine —
    the 8-byte-key experiments stay legal."""
    bad = []
    for eqn in walk_eqns(jaxpr):
        wide_out = [v for v in eqn.outvars if _itemsize(v.aval) >= WIDE_BYTES]
        if not wide_out:
            continue
        if any(_itemsize(v.aval) >= WIDE_BYTES for v in eqn.invars):
            continue
        # iota/full-style creation from static params is a choice, not a
        # promotion, but it still widens the pipeline: report it too.
        avals = ", ".join(str(v.aval) for v in wide_out)
        bad.append(f"{eqn.primitive.name} -> {avals}")
    return tuple(bad)


# ---------------------------------------------------------------------------
# liveness watermark
# ---------------------------------------------------------------------------
def _var_bytes(v) -> int:
    aval = getattr(v, "aval", None)
    shape = getattr(aval, "shape", None)
    if shape is None:
        return 0
    size = 1
    for d in shape:
        if not isinstance(d, int):  # symbolic dim: can't price statically
            return 0
        size *= d
    return size * _itemsize(aval)


def _roots_bytes(jaxpr) -> int:
    seen, total = set(), 0
    for v in list(jaxpr.invars) + list(jaxpr.constvars):
        if id(v) not in seen:
            seen.add(id(v))
            total += _var_bytes(v)
    return total


def liveness_peak(jaxpr, _cache=None) -> tuple[int, str]:
    """(peak_live_bytes, primitive_at_peak) for a jaxpr, by last-use
    liveness over its eqns. Sub-jaxpr eqns contribute their own internal
    peak (beyond their inputs, which are live at this level already) at
    the point of the call — scan/while bodies are priced once, like the
    budget. An upper bound on residency: XLA may fuse intermediates away,
    but it cannot make a materialization the jaxpr never wrote."""
    jaxpr = _as_jaxpr(jaxpr)
    cache = {} if _cache is None else _cache
    key = id(jaxpr)
    if key in cache:
        return cache[key]

    last_use: dict = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if not isinstance(v, jcore.Literal):
                last_use[v] = i
    for v in jaxpr.outvars:
        if not isinstance(v, jcore.Literal):
            last_use[v] = len(jaxpr.eqns)

    live: dict = {}
    for v in list(jaxpr.invars) + list(jaxpr.constvars):
        live[v] = _var_bytes(v)
    live_bytes = sum(live.values())
    peak, peak_at = live_bytes, "<args>"

    for i, eqn in enumerate(jaxpr.eqns):
        inner_extra = 0
        for sub in subjaxprs(eqn):
            sub_peak, _ = liveness_peak(sub, cache)
            inner_extra += max(0, sub_peak - _roots_bytes(sub))
        out_bytes = sum(_var_bytes(v) for v in eqn.outvars)
        here = live_bytes + out_bytes + inner_extra
        if here > peak:
            peak, peak_at = here, eqn.primitive.name
        for v in eqn.outvars:
            if last_use.get(v, -1) > i and v not in live:
                live[v] = _var_bytes(v)
                live_bytes += live[v]
        for v in eqn.invars:
            if (not isinstance(v, jcore.Literal) and last_use.get(v) == i
                    and v in live):
                live_bytes -= live.pop(v)

    cache[key] = (peak, peak_at)
    return peak, peak_at


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------
def audit_jaxpr(jaxpr) -> AuditReport:
    """Full audit of a (Closed)Jaxpr: budget + watermark + promotions."""
    raw = _as_jaxpr(jaxpr)
    peak, peak_at = liveness_peak(raw)
    return AuditReport(
        budget=budget_of_jaxpr(raw),
        peak_live_bytes=peak,
        peak_live_at=peak_at,
        arg_bytes=_roots_bytes(raw),
        out_bytes=sum(_var_bytes(v) for v in raw.outvars
                      if not isinstance(v, jcore.Literal)),
        promotions=find_promotions(raw),
    )


def audit_fn(fn, *args, **kwargs) -> AuditReport:
    """Trace `fn(*args, **kwargs)` and audit the resulting jaxpr."""
    return audit_jaxpr(jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args))


def budget_of(fn, *args, **kwargs) -> PrimitiveBudget:
    return audit_fn(fn, *args, **kwargs).budget


def count_sorts(fn_or_jaxpr, *args, **kwargs) -> int:
    """Shared test API (replaces the per-test-file `_count_sorts` copies):
    sort-primitive count of a jaxpr, or of `fn(*args)` traced."""
    if _as_jaxpr(fn_or_jaxpr) is not None:
        return budget_of_jaxpr(fn_or_jaxpr).sorts
    return budget_of(fn_or_jaxpr, *args, **kwargs).sorts
