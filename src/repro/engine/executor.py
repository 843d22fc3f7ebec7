"""Physical-plan interpreter over `core` operators — jit-compatible.

All plan structure (operator order, algorithms, capacities) is Python-side
and static; only the tables flow through as traced pytrees, so the whole
plan compiles as one XLA program:

    compiled = jax.jit(lambda tables: execute(plan.root, tables))

Every operator follows the repo's static-shape contract (DESIGN.md §2):
it consumes and produces `(Table-with-capacity, valid_count)` pairs. Rows
at index >= count are padding; before each key-consuming operator the key
column is re-masked to KEY_SENTINEL so padding can never match or form a
group. Filters compact survivors to the front, which preserves the
clustering GFTR relies on (`primitives.compact` is stable).
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from typing import Mapping

import jax
import jax.numpy as jnp

from repro.core import group_aggregate, join, phj_groupjoin
from repro.core import primitives as prim
from repro.core.groupby import groupby_partition_checked
from repro.core.groupjoin import groupjoin_checked
from repro.core.hash_join import phj_join_checked
from repro.core.table import KEY_SENTINEL, Table, concat_tables
from repro.obs import metrics
from repro.resilience import escalation, faults

from . import membudget
from . import physical as P
from .logical import FILTER_OP_FNS


class CapacitySaturated(RuntimeError):
    """A result's valid rows reached (or overflowed) its static capacity:
    the result is *suspected* truncated (capacity clamping makes real
    truncation indistinguishable from an exact fit), so the run is treated
    as failed and retried with more headroom."""


def degradable(e: BaseException) -> bool:
    """True for the failures a degraded re-plan can fix: an exhausted
    escalation ladder, a saturated capacity, an injected fault, or an
    allocation failure. Everything else — a programming error, or a kernel
    that fails to lower or compile — fails the same way on every plan and
    must surface instead of being masked by a slower plan (DESIGN.md §13)."""
    return (isinstance(e, (escalation.EscalationExhausted, CapacitySaturated,
                           faults.FaultInjected))
            or membudget.is_memory_error(e))


# Checked mode: capacity-sensitive operators run through their resilience
# ladders (phj_join_checked / groupby_partition_checked / groupjoin_checked)
# instead of the plain drivers, so a plan whose capacities were misestimated
# escalates and records EscalationReports rather than silently truncating.
# Ladders read overflow flags host-side, so this is only legal in EAGER
# execution — `run(jit=False)` and the tracer's validation pass set it; the
# jitted fast path never does (its protection is the degrade-once retry).
_CHECKED = contextvars.ContextVar("repro_executor_checked", default=False)


@contextlib.contextmanager
def checked_mode():
    token = _CHECKED.set(True)
    try:
        yield
    finally:
        _CHECKED.reset(token)


def _can_check(*arrays) -> bool:
    """Checked mode is armed AND the inputs are concrete. The ladders'
    overflow checks are host-side bool()s on device scalars, impossible on
    tracers — an eager `run(jit=False)` wrapped in an OUTER jax.jit (the
    benchmarks do this to time the interpreted plan as one executable)
    must fall back to the plain drivers: the identical computation the
    jit path compiles, protected by the degrade-once retry instead."""
    return _CHECKED.get() and not any(
        isinstance(a, jax.core.Tracer) for a in arrays)


class Materialized:
    """Pseudo plan node wrapping an already-computed ``(Table, count)``
    pair. The per-node tracer (repro.obs.trace) substitutes these for a
    node's children so `execute` times exactly one operator while its
    inputs arrive as traced jit arguments. Untraced execution never
    constructs one."""

    __slots__ = ("value",)

    def __init__(self, value):
        self.value = value

    def children(self):
        return ()


def _valid_mask(table: Table, count) -> jax.Array:
    return jnp.arange(table.num_rows, dtype=jnp.int32) < count


def _mask_key(table: Table, count, key: str) -> Table:
    """Force padding rows' key to KEY_SENTINEL so joins/group-bys drop them."""
    k = table[key]
    masked = jnp.where(_valid_mask(table, count), k,
                       jnp.asarray(KEY_SENTINEL, k.dtype))
    return table.with_columns(**{key: masked})


def execute(node: P.PhysNode, tables: Mapping[str, Table], counts=None):
    """Interpret the plan bottom-up. Returns (Table, valid_count).

    `counts` (optional ``{table_name: valid_count}``) is the serving
    layer's capacity-bucketing hook (DESIGN.md §14): tables padded up to a
    shared capacity bucket flow through with their TRUE valid counts as
    traced scalars, so one compiled executable serves every dataset that
    pads to the same bucket. Without it, a scan's whole table is valid —
    the one-shot contract every existing call site relies on."""
    if isinstance(node, Materialized):
        return node.value
    if isinstance(node, P.PScan):
        t = tables[node.table]
        if counts is not None and node.table in counts:
            return t, jnp.asarray(counts[node.table], jnp.int32)
        return t, jnp.asarray(t.num_rows, jnp.int32)
    if isinstance(node, P.PFilter):
        return _filter(node, tables, counts)
    if isinstance(node, P.PProject):
        t, count = execute(node.child, tables, counts)
        return t.select(node.columns), count
    if isinstance(node, P.PJoin):
        return _join(node, tables, counts)
    if isinstance(node, P.PGroupBy):
        return _group_by(node, tables, counts)
    if isinstance(node, P.PGroupJoin):
        return _group_join(node, tables, counts)
    if isinstance(node, P.POrderByLimit):
        return _order_by(node, tables, counts)
    raise TypeError(f"unknown physical node {type(node).__name__}")


def _filter(node: P.PFilter, tables, counts=None):
    t, count = execute(node.child, tables, counts)
    mask = FILTER_OP_FNS[node.op](t[node.column], node.value) & _valid_mask(t, count)
    names = t.column_names
    outs, new_count = prim.compact(mask, [t[n] for n in names], node.capacity)
    return Table(dict(zip(names, outs))), new_count


def _join(node: P.PJoin, tables, counts=None):
    bt, b_count = execute(node.build, tables, counts)
    pt, p_count = execute(node.probe, tables, counts)
    bt = _mask_key(bt, b_count, node.build_key)
    pt = _mask_key(pt, p_count, node.probe_key)
    # core.join wants one shared key name: align build's key to the probe's
    if node.build_key != node.probe_key:
        bt = bt.rename({node.build_key: node.probe_key})
    if node.algorithm == "phj" and _can_check(bt[node.probe_key],
                                              pt[node.probe_key]):
        out, count = phj_join_checked(
            bt, pt, key=node.probe_key, pattern=node.pattern,
            out_size=node.capacity, mode=node.mode,
        )
    else:
        out, count = join(
            bt, pt, key=node.probe_key, algorithm=node.algorithm,
            pattern=node.pattern, out_size=node.capacity, mode=node.mode,
        )
    if node.build_key != node.probe_key:
        # restore the equal-valued alias column (schema contract)
        out = out.with_columns(**{node.build_key: out[node.probe_key]})
    return out, count


def _group_by(node: P.PGroupBy, tables, counts=None):
    t, count = execute(node.child, tables, counts)
    t = _mask_key(t, count, node.key)
    sel = t.select((node.key,) + tuple(c for c, _ in node.aggs))
    if node.strategy == "partition" and _can_check(sel[node.key]):
        return groupby_partition_checked(
            sel, key=node.key, aggs=dict(node.aggs),
            num_groups=node.capacity, **dict(node.agg_kw),
        )
    return group_aggregate(
        sel, key=node.key, aggs=dict(node.aggs), num_groups=node.capacity,
        strategy=node.strategy, **dict(node.agg_kw),
    )


def _group_join(node: P.PGroupJoin, tables, counts=None):
    """Fused join + grouped aggregation: the probe's matches feed the
    accumulator directly (core.groupjoin), so only the key, group-key, and
    aggregate-input columns are ever touched — the join output never
    exists."""
    bt, b_count = execute(node.build, tables, counts)
    pt, p_count = execute(node.probe, tables, counts)
    bt = _mask_key(bt, b_count, node.build_key)
    pt = _mask_key(pt, p_count, node.probe_key)
    key = node.probe_key
    if node.build_key != key:
        bt = bt.rename({node.build_key: key})
    agg_cols = [c for c, _ in node.aggs]
    b_need = dict.fromkeys([key] + [c for c in agg_cols if c in bt])
    p_need = dict.fromkeys([key, node.probe_group_key]
                           + [c for c in agg_cols if c in pt])
    if _can_check(bt[key], pt[key]):
        out, count = groupjoin_checked(
            bt.select(tuple(b_need)), pt.select(tuple(p_need)), key=key,
            group_key=node.probe_group_key, aggs=dict(node.aggs),
            num_groups=node.capacity, agg_strategy=node.agg_strategy,
            agg_kw=dict(node.agg_kw) or None,
        )
    else:
        out, count = phj_groupjoin(
            bt.select(tuple(b_need)), pt.select(tuple(p_need)), key=key,
            group_key=node.probe_group_key, aggs=dict(node.aggs),
            num_groups=node.capacity, agg_strategy=node.agg_strategy,
            agg_kw=dict(node.agg_kw) or None,
        )
    if node.group_key != node.probe_group_key:
        # logical schema names the group column after the GroupBy key (the
        # equal-valued build-key alias); restore it
        out = out.rename({node.probe_group_key: node.group_key})
    return out, count


def _order_by(node: P.POrderByLimit, tables, counts=None):
    t, count = execute(node.child, tables, counts)
    k = t[node.key]
    if node.descending:
        # bitwise complement reverses integer order without the INT_MIN
        # overflow of arithmetic negation; floats negate safely
        k = ~k if jnp.issubdtype(k.dtype, jnp.integer) else -k
    # validity is the primary sort key, so padding rows land strictly after
    # every valid row no matter what values they carry
    invalid = (~_valid_mask(t, count)).astype(jnp.int32)
    iota = jnp.arange(t.num_rows, dtype=jnp.int32)
    _, _, perm = jax.lax.sort((invalid, k, iota), num_keys=2, is_stable=True)
    # slice the permutation before gathering: top-k needs a capacity-length
    # gather, not a full-table copy of every column
    out = t.take(perm[:node.capacity])
    return out, jnp.minimum(count, node.capacity)


# ---------------------------------------------------------------------------
# contract audit: the compiled side of priced-vs-compiled (DESIGN.md §11)
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class NodeAudit:
    """One physical node judged against its priced contract. `own_budget`
    is the node's incremental primitive budget: its subtree's trace minus
    its children's subtree traces, so a join is never charged for the sort
    its order-by child pays."""
    node: P.PhysNode
    contract: object  # analysis.OperatorContract
    report: object  # analysis.AuditReport of the node's SUBTREE
    own_budget: object  # analysis.PrimitiveBudget of the node alone
    violations: list


@dataclasses.dataclass
class PlanAudit:
    entries: list  # NodeAudit, preorder from the root
    root_report: object  # whole-plan AuditReport

    @property
    def violations(self) -> list:
        return [v for e in self.entries for v in e.violations]

    def by_node(self) -> dict:
        return {id(e.node): e for e in self.entries}

    def as_dict(self) -> dict:
        return {
            "peak_live_bytes": self.root_report.peak_live_bytes,
            "budget": self.root_report.budget.as_dict(),
            "nodes": [{
                "node": type(e.node).__name__,
                "contract": e.contract.describe(),
                "compiled": e.own_budget.as_dict(),
                "violations": [f"{type(v).__name__}: {v}"
                               for v in e.violations],
            } for e in self.entries],
        }


def _scan_names(node: P.PhysNode) -> set:
    if isinstance(node, P.PScan):
        return {node.table}
    names: set = set()
    for child in node.children():
        names |= _scan_names(child)
    return names


def audit(plan: "P.PhysicalPlan",
          tables: Mapping[str, Table] | None = None) -> PlanAudit:
    """Trace every plan subtree, attribute each node's incremental
    primitive budget, and judge it against the node's declared contract
    (`analysis.contracts.contract_for_node`). The subtree traces use only
    the tables that subtree scans, so the liveness watermark of a fused
    group-join reflects *its* inputs — the checkable form of 'the join
    output never materialized'."""
    from repro.analysis import contracts as C
    from repro.analysis import jaxpr_audit as A

    metrics.counter("engine.contract_audits").inc()
    tables = dict(tables if tables is not None else plan.catalog.tables)
    reports: dict = {}

    def trace(node: P.PhysNode):
        sub = {n: tables[n] for n in sorted(_scan_names(node))}
        closed = jax.make_jaxpr(lambda tb: execute(node, tb))(sub)
        return A.audit_jaxpr(closed)

    entries: list[NodeAudit] = []

    def visit(node: P.PhysNode):
        rep = trace(node)
        reports[id(node)] = rep
        contract = C.contract_for_node(node)
        entry = NodeAudit(node=node, contract=contract, report=rep,
                          own_budget=None, violations=[])
        entries.append(entry)  # preorder: parent precedes children
        own = rep.budget
        for child in node.children():
            visit(child)
            own = own - reports[id(child)].budget
        entry.own_budget = own
        entry.violations = C.check(contract, rep, own)

    visit(plan.root)
    return PlanAudit(entries=entries, root_report=reports[id(plan.root)])


def run(plan: "P.PhysicalPlan", tables: Mapping[str, Table] | None = None,
        *, jit: bool = True, trace: bool = False, trace_iters: int = 1,
        trace_warmup: int = 1, counts=None):
    """Execute a PhysicalPlan. `tables` defaults to the catalog's; pass new
    same-shape tables to reuse one compiled plan across datasets. The jitted
    executor is cached on the plan, so repeated `run()` calls trace and
    compile once.

    `counts` ({table_name: valid_count}) enables capacity bucketing
    (DESIGN.md §14): the counts ride as traced int32 scalars into a
    SEPARATE cached executable (`plan.compiled_bucketed`), so one compiled
    plan serves every dataset padded to its capacity buckets — the
    count-free `plan.compiled` artifact and its jaxpr (pinned by
    tests/test_obs.py) are untouched.

    With ``trace=True`` the plan runs node by node under the span tracer
    (repro.obs.trace) and returns ``(table, count, QueryTrace)`` — per-node
    device-synced wall times, rows/bytes, and predicted-vs-measured
    residuals. Tracing is strictly opt-in: the untraced path below is the
    exact pre-trace code path (no Span allocation, identical whole-plan
    jaxpr — pinned by tests/test_obs.py).

    Graceful degradation (DESIGN.md §13): if the plan raises a `degradable`
    failure at trace or run time — an `EscalationExhausted` ladder, a
    saturated capacity, an allocation failure, a fault-injected
    `raise:executor.run` — the executor re-plans ONCE via
    `physical.degrade_plan` (doubled capacities, sort/smj strategies) and
    reruns. Every other error (programming errors, kernels that fail to
    lower or compile) and failures of an already-degraded plan re-raise
    untouched."""
    if trace:
        if counts is not None:
            raise ValueError("trace=True does not support counts= (the "
                             "span tracer materializes per-node inputs)")
        from repro.obs.trace import trace_execute

        return trace_execute(plan, tables, iters=trace_iters,
                             warmup=trace_warmup)
    tables = dict(tables if tables is not None else plan.catalog.tables)

    def attempt(p: "P.PhysicalPlan"):
        faults.check_site("executor.run")
        faults.check_oom("executor.run")
        if p.morsel_factor > 1:
            # memory rung (DESIGN.md §15): out-of-core morsel driver
            return run_morsels(p, tables, counts=counts, jit=jit)
        if not jit:
            # eager runs are the diagnostic path: capacity-sensitive nodes
            # go through their resilience ladders and record reports
            with checked_mode():
                return execute(p.root, tables, counts)
        if counts is not None:
            if p.compiled_bucketed is None:
                p.compiled_bucketed = jax.jit(
                    lambda tb, ct: execute(p.root, tb, ct))
                metrics.counter("engine.plans_compiled").inc()
            else:
                metrics.counter("engine.plan_cache_hits").inc()
            ct = {k: jnp.asarray(v, jnp.int32) for k, v in counts.items()}
            return p.compiled_bucketed(tables, ct)
        if p.compiled is None:
            p.compiled = jax.jit(lambda tb: execute(p.root, tb))
            metrics.counter("engine.plans_compiled").inc()
        else:
            metrics.counter("engine.plan_cache_hits").inc()
        return p.compiled(tables)

    try:
        return attempt(plan)
    except Exception as e:  # noqa: BLE001 — degradable failures degrade once
        if plan.degraded or not degradable(e):
            raise
        reason = f"{type(e).__name__}: {e}"[:120]
        if plan.degraded_plan is None:
            # allocation failures route onto the MEMORY rung when the plan
            # is splittable — a smaller working set, never the default
            # rung's doubled capacities (DESIGN.md §15)
            if (membudget.is_memory_error(e)
                    and P.morsel_axis(plan.root) is not None):
                plan.degraded_plan = P.degrade_plan(plan, reason, memory=True)
            else:
                plan.degraded_plan = P.degrade_plan(plan, reason)
        metrics.counter("resilience.plan_degradations").inc()
        escalation.record_degradation("executor", reason)
        return attempt(plan.degraded_plan)


# ---------------------------------------------------------------------------
# morsel-driven out-of-core execution (DESIGN.md §15)
# ---------------------------------------------------------------------------
def run_morsels(plan: "P.PhysicalPlan",
                tables: Mapping[str, Table] | None = None, *,
                counts=None, factor: int | None = None, jit: bool = True):
    """Execute `plan` out-of-core: split the morsel axis (the probe spine's
    base scan, `physical.morsel_axis`) into `factor` equal chunks, run the
    capacity-scaled per-morsel clone (`physical.morsel_plan`) over each
    chunk through ONE compiled bucketed executable — chunk validity rides
    in as a traced count scalar, so every morsel reuses the same
    compilation — and recombine host-side: concat for row-shaped roots,
    a partial-aggregate merge for group roots (sum/count/min/max
    re-reduce; mean = merged sum / merged count, the exact `_finalize`
    expression). Returns (Table, valid_count) shaped exactly like
    whole-plan `run`."""
    factor = int(factor if factor is not None else plan.morsel_factor)
    if factor < 2:
        raise ValueError(f"morsel factor must be >= 2, got {factor}")
    axis = P.morsel_axis(plan.root)
    if axis is None:
        raise ValueError("plan has no morsel axis (not splittable)")
    tables = dict(tables if tables is not None else plan.catalog.tables)
    axis_table = tables[axis]
    rows = axis_table.num_rows
    total = int(counts[axis]) if counts is not None and axis in counts else rows
    mp = P.morsel_plan(plan, factor, rows=rows)
    m = P.morsel_rows(rows, factor)
    padded = axis_table.pad_to(m * factor)
    base_counts = dict(counts) if counts is not None else {}
    parts = []
    for i in range(factor):
        cnt = min(max(total - i * m, 0), m)
        if cnt == 0 and i > 0:
            continue  # past the valid tail; morsel 0 always runs so an
            # empty input still yields a well-formed empty result
        chunk = Table({n: v[i * m:(i + 1) * m]
                       for n, v in padded.columns.items()})
        mtables = dict(tables)
        mtables[axis] = chunk
        mcounts = dict(base_counts)
        mcounts[axis] = cnt
        metrics.counter("engine.morsel_runs").inc()
        parts.append(run(mp, mtables, jit=jit, counts=mcounts))
    return _recombine(plan.root, parts)


def _recombine(root: P.PhysNode, parts: list):
    """Merge per-morsel results into the whole-plan (Table, count)."""
    sliced = [(t.head(int(c)), int(c)) for t, c in parts]
    if isinstance(root, (P.PGroupBy, P.PGroupJoin)):
        return _merge_partials(root, sliced)
    # row-shaped root (join/filter/project/scan spine): morsels partition
    # the probe, so valid rows concatenate — total is the whole-plan count
    # and fits the root capacity whenever the whole plan would have
    total = sum(c for _, c in sliced)
    if total > root.capacity:
        raise CapacitySaturated(
            f"morsel recombine overflow: {total} rows exceed the root "
            f"capacity {root.capacity}")
    cat = concat_tables([t for t, _ in sliced])
    return cat.pad_to(root.capacity), jnp.asarray(total, jnp.int32)


def _merge_partials(root, sliced):
    """Re-reduce per-morsel partial aggregates (the `partial_agg_plan`
    rewrite) into final aggregates, bit-identical to the whole-plan
    result: integer sums/counts/min/max are associative, and mean divides
    the merged sum by the merged count with the exact `_finalize`
    expression (`acc / max(count,1).astype(acc.dtype)`)."""
    key = root.key if isinstance(root, P.PGroupBy) else root.group_key
    partial, count_col = P.partial_agg_plan(root)
    combine = {"sum": "sum", "count": "sum", "min": "min", "max": "max"}
    cat = concat_tables([t for t, _ in sliced])
    merged, count = group_aggregate(
        cat, key=key,
        aggs={f"{c}_{pop}": combine[pop] for c, pop in partial},
        num_groups=root.capacity, strategy="sort",
    )

    def final(c, op):
        if op == "mean":
            s = merged[f"{c}_sum_sum"]
            n = merged[f"{count_col}_count_sum"]
            return s / jnp.maximum(n, 1).astype(s.dtype)
        pop = dict(partial)[c]
        return merged[f"{c}_{pop}_{combine[pop]}"]

    out = {key: merged[key]}
    out.update({f"{c}_{op}": final(c, op) for c, op in root.aggs})
    return Table(out).select(root.columns), count


def plan_peak_bytes(plan: "P.PhysicalPlan",
                    tables: Mapping[str, Table] | None = None,
                    counts=None) -> int:
    """The plan's whole-program peak-live-bytes watermark (the byte the
    memory governor admits against), from a single root trace — the cheap
    subset of `audit()` (which traces every subtree to attribute per-node
    budgets). With `counts`, traces the bucketed form the serving layer
    actually runs."""
    from repro.analysis import jaxpr_audit as A

    tables = dict(tables if tables is not None else plan.catalog.tables)
    if counts is not None:
        ct = {k: jnp.asarray(v, jnp.int32) for k, v in counts.items()}
        closed = jax.make_jaxpr(
            lambda tb, c: execute(plan.root, tb, c))(tables, ct)
    else:
        closed = jax.make_jaxpr(lambda tb: execute(plan.root, tb))(tables)
    return int(A.audit_jaxpr(closed).peak_live_bytes)
