"""The static analyzer itself: primitive budgets through every sub-jaxpr
kind, the liveness watermark, the dtype contract, the Pallas kernel lint,
env-knob validation, and — most importantly — the negative space: tiny
deliberately-violating programs must each trip their specific
`ContractViolation` subclass, and `explain(verify=True)` must catch an
injected priced-vs-compiled divergence end to end."""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl
import jax.numpy as jnp
import numpy as np
import pytest

from repro import analysis
from repro.analysis import (DtypePromotionViolation, FloatScatterViolation, GridAliasViolation,
                            MaterializationViolation, OperatorContract, PrimitiveBudget,
                            SortBudgetViolation, VmemBudgetViolation, audit_fn, budget_of,
                            count_sorts, kernel_lint)


# ---------------------------------------------------------------------------
# budget counting
# ---------------------------------------------------------------------------
def test_budget_counts_primitives():
    def fn(x):
        srt = jnp.sort(x)
        idx = jnp.argsort(x)  # lowers to sort as well
        gath = jnp.take(srt, idx)
        scat = jnp.zeros_like(x).at[idx].set(gath)
        sadd = jnp.zeros_like(x).at[idx].add(gath)
        return scat + sadd

    b = budget_of(fn, jnp.arange(16.0))
    assert b.sorts == 2
    assert b.gathers == 1
    assert b.scatters == 1
    assert b.scatter_adds == 1
    assert b.float_scatter_adds == 1  # float operand -> flagged as float


def test_budget_recurses_into_pjit_scan_cond_while():
    def fn(x):
        y = jax.jit(jnp.sort)(x)  # pjit body

        def body(c, t):
            return c + jnp.sort(t), t

        c, _ = jax.lax.scan(body, y, jnp.stack([x, x]))  # scan body
        c = jax.lax.cond(c.sum() > 0, jnp.sort, lambda a: a, c)  # branches
        return jax.lax.while_loop(
            lambda s: s.sum() > 1e9, lambda s: jnp.sort(s), c)  # while body

    b = budget_of(fn, jnp.arange(8.0))
    # one per nesting level; scan/while bodies count ONCE (static shape,
    # like the cost model prices them), cond counts each branch's content
    assert b.sorts == 4


def test_budget_add_sub_compose():
    a = PrimitiveBudget(sorts=2, gathers=3)
    b = PrimitiveBudget(sorts=1, gathers=1, scatters=5)
    assert (a + b).sorts == 3 and (a + b).scatters == 5
    assert (a - b).sorts == 1 and (a - b).gathers == 2


def test_count_sorts_accepts_fn_and_jaxpr():
    fn = lambda x: jnp.sort(x)  # noqa: E731
    assert count_sorts(fn, jnp.arange(8.0)) == 1
    closed = jax.make_jaxpr(fn)(jnp.arange(8.0))
    assert count_sorts(closed) == 1
    assert count_sorts(closed.jaxpr) == 1  # raw Jaxpr too (old helper API)


def test_pallas_call_counted_and_body_walked():
    from repro.kernels.histogram import histogram_pallas

    b = budget_of(functools.partial(histogram_pallas, num_bins=16),
                  jnp.arange(1024, dtype=jnp.int32) % 16)
    assert b.pallas_calls == 1


# ---------------------------------------------------------------------------
# liveness watermark
# ---------------------------------------------------------------------------
def test_liveness_peak_sees_large_intermediate():
    def fn(x):
        big = jnp.tile(x, 4096)  # 8 * 4096 * 4B = 128 KiB intermediate
        return big.sum()

    rep = audit_fn(fn, jnp.arange(8, dtype=jnp.float32))
    assert rep.peak_live_bytes >= 8 * 4096 * 4
    assert rep.out_bytes == 4  # scalar out


def test_liveness_peak_drops_dead_values():
    def fn(x):
        a = x * 2  # dead after b
        b = a + 1
        return b.sum()

    rep = audit_fn(fn, jnp.arange(1024, dtype=jnp.float32))
    # never more than ~3 arrays of x's size live at once
    assert rep.peak_live_bytes <= 3 * 1024 * 4 + 64


# ---------------------------------------------------------------------------
# negative space: each violation class fires on its minimal trigger
# ---------------------------------------------------------------------------
def test_sneaky_sort_trips_sort_budget():
    """A 'sort-free' contract over a plan that sneaks one in."""
    def sneaky(x):
        return jnp.take(x, jnp.argsort(x))  # a hidden sort

    rep = audit_fn(sneaky, jnp.arange(32, dtype=jnp.int32))
    contract = analysis.join_contract("phj")  # priced: zero sorts
    with pytest.raises(SortBudgetViolation):
        analysis.enforce(contract, rep)


def test_f64_promotion_trips_dtype_contract():
    jax.config.update("jax_enable_x64", True)
    try:
        def promotes(x):
            return x.astype(jnp.float64) * 2.0  # silent widening

        rep = audit_fn(promotes, jnp.arange(8, dtype=jnp.float32))
        assert rep.promotions
        with pytest.raises(DtypePromotionViolation):
            analysis.enforce(OperatorContract(name="int32-pipeline"), rep)

        # deliberate 64-bit inputs stay legal (8-byte key experiments)
        rep64 = audit_fn(lambda x: x * 2, jnp.arange(8, dtype=jnp.int64))
        assert not rep64.promotions
    finally:
        jax.config.update("jax_enable_x64", False)


def test_float_scatter_add_outside_approved_paths_trips():
    def accumulates(v):
        return jnp.zeros((8,), jnp.float32).at[v.astype(jnp.int32) % 8].add(v)

    rep = audit_fn(accumulates, jnp.arange(32, dtype=jnp.float32))
    contract = analysis.join_contract("phj")  # joins: no float accumulation
    with pytest.raises(FloatScatterViolation):
        analysis.enforce(contract, rep)


def test_over_vmem_block_spec_trips_lint():
    def big_block(x):
        def kern(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        return pl.pallas_call(
            kern,
            out_shape=jax.ShapeDtypeStruct((8192, 1024), jnp.float32),
            grid=(2,),
            in_specs=[pl.BlockSpec((4096, 1024), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((4096, 1024), lambda i: (i, 0)),
            interpret=True,
        )(x)

    x = jnp.zeros((8192, 1024), jnp.float32)  # trace-only, never executed
    reports = kernel_lint.lint_fn(big_block, x)
    assert any(isinstance(v, VmemBudgetViolation)
               for r in reports for v in r.violations)
    with pytest.raises(VmemBudgetViolation):
        kernel_lint.enforce(reports)


def test_aliased_grid_output_trips_lint_unless_declared():
    def aliased(x):
        def kern(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        return pl.pallas_call(
            kern,
            out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
            grid=(4,),
            in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),  # every step
            interpret=True,
        )(x)

    x = jnp.zeros((32, 128), jnp.float32)
    reports = kernel_lint.lint_fn(aliased, x)
    assert any(isinstance(v, GridAliasViolation)
               for r in reports for v in r.violations)
    # the same kernel with accumulation declared is a stated contract
    declared = kernel_lint.lint_fn(aliased, x, allow_output_revisit=True)
    assert not any(r.violations for r in declared)
    assert declared[0].aliased_output_blocks == 1
    # a declaration names kernel bodies: only the named one may revisit
    assert declared[0].name.endswith("/kern")
    named = kernel_lint.lint_fn(aliased, x, allow_output_revisit=("kern",))
    assert not any(r.violations for r in named)
    other = kernel_lint.lint_fn(aliased, x, allow_output_revisit=("other",))
    assert any(isinstance(v, GridAliasViolation)
               for r in other for v in r.violations)


def test_materialization_bound_trips_on_fat_residency():
    def materializes(x):
        fat = jnp.tile(x, 8192)  # 32 MiB live off a 4 KiB input
        return fat.sum()

    rep = audit_fn(materializes, jnp.arange(1024, dtype=jnp.float32))
    contract = OperatorContract(name="fused", live_multiplier=4.0,
                                live_slack_bytes=1 << 20)
    with pytest.raises(MaterializationViolation):
        analysis.enforce(contract, rep)


# ---------------------------------------------------------------------------
# production kernels lint clean
# ---------------------------------------------------------------------------
def test_production_kernels_lint_clean():
    reports = analysis.lint_production_kernels()
    assert reports, "registry must cover the production kernels"
    for rep in reports:
        assert not rep.violations, (rep.name, rep.violations)
        assert rep.vmem_bytes <= rep.vmem_budget
    # histogram's sequential accumulation is exercised AND declared
    hist = [r for r in reports if r.name.startswith("histogram")]
    assert hist and hist[0].aliased_output_blocks >= 1
    # partition_ranks' rank kernel is not covered by the block-histogram
    # kernel's declaration: it is linted as an injective output map
    assert "partition_ranks/_rank_kernel" in {r.name for r in reports}


# ---------------------------------------------------------------------------
# env-knob validation (read-time, never frozen at import)
# ---------------------------------------------------------------------------
def test_partition_plan_impl_env_validated(monkeypatch):
    from repro.core import primitives as prim
    from repro.kernels import ops as kops

    monkeypatch.setenv("REPRO_PARTITION_PLAN_IMPL", "fancy")
    with pytest.raises(ValueError, match="pallas/xla"):
        kops.partition_plan_impl()
    with pytest.raises(ValueError, match="REPRO_PARTITION_PLAN_IMPL"):
        kops.PARTITION_PLAN_IMPL  # noqa: B018 - the legacy attribute too
    digits = jnp.arange(32, dtype=jnp.int32) % 4
    with pytest.raises(ValueError, match="REPRO_PARTITION_PLAN_IMPL"):
        prim.plan_partition_permutation(digits, 4)  # impl=None resolves env
    # explicit impl= bypasses the env entirely
    prim.plan_partition_permutation(digits, 4, impl="pallas")
    monkeypatch.setenv("REPRO_PARTITION_PLAN_IMPL", "xla")
    assert kops.partition_plan_impl() == "xla"


def test_pallas_interpret_env_validated(monkeypatch):
    from repro.kernels import common

    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "maybe")
    with pytest.raises(ValueError, match="REPRO_PALLAS_INTERPRET"):
        common.default_interpret()
    with pytest.raises(ValueError, match="allowed"):
        common.resolve_interpret(None)
    # an explicit flag still wins without consulting the env
    assert common.resolve_interpret(True) is True
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "YES")  # case-insensitive
    assert common.default_interpret() is True


# ---------------------------------------------------------------------------
# engine integration: explain(verify=True) end to end
# ---------------------------------------------------------------------------
def _plan(rng):
    from repro.core import Table
    from repro.engine import Catalog, optimize, scan

    n_r, n_s = 256, 2048
    R = Table({"k": jnp.asarray(rng.permutation(n_r).astype(np.int32)),
               "rv": jnp.asarray(rng.integers(0, 100, n_r).astype(np.int32))})
    S = Table({"k": jnp.asarray(rng.integers(0, n_r, n_s).astype(np.int32)),
               "g": jnp.asarray(rng.integers(0, 32, n_s).astype(np.int32)),
               "sv": jnp.asarray(rng.integers(0, 100, n_s).astype(np.int32))})
    cat = Catalog({"R": R, "S": S})
    q = (scan("S").join(scan("R"), key="k")
         .group_by("g", rv="sum", sv="mean"))
    return optimize(q, cat, measure_profile=False,
                    force_join=("phj", "gftr"))


def test_explain_verify_renders_priced_vs_compiled(rng):
    plan = _plan(rng)
    text = plan.explain(verify=True)
    assert "priced[" in text and "compiled[" in text
    assert "peak-live=" in text
    assert "DIVERGED" not in text
    # plain explain stays cheap and unannotated
    assert "priced[" not in plan.explain()


def test_explain_verify_raises_on_injected_violation(rng, monkeypatch):
    """Flip the partition planner to its sort-based reference arm under a
    plan the model priced as sort-free: the compiled jaxpr now contains
    sorts the contract forbids, and verify must catch the divergence."""
    plan = _plan(rng)
    monkeypatch.setenv("REPRO_PARTITION_PLAN_IMPL", "xla")
    with pytest.raises(SortBudgetViolation):
        plan.explain(verify=True)


def test_executor_audit_attributes_node_budgets(rng):
    from repro.engine import executor

    plan = _plan(rng)
    plan_audit = executor.audit(plan)
    assert not plan_audit.violations
    kinds = {type(e.node).__name__: e for e in plan_audit.entries}
    assert "PJoin" in kinds and "PGroupBy" in kinds
    # the join's own budget is sort-free even though the subtree includes
    # scans; the group-by's own budget excludes the join's gathers
    assert kinds["PJoin"].own_budget.sorts == 0
    assert kinds["PGroupBy"].own_budget.gathers \
        <= kinds["PGroupBy"].report.budget.gathers
    d = plan_audit.as_dict()
    assert d["nodes"] and d["budget"]["sorts"] == 0
