"""Memory-governed execution (DESIGN.md §15): byte budget + reservation
ledger, the `oom:` fault family, morsel-driven out-of-core execution, and
the §4.4 memory-model ledger in explain()."""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import Table
from repro.core import memmodel
from repro.data import relgen
from repro.engine import (Catalog, MemoryBudget, MemoryBudgetExceeded,
                          detect_budget_bytes, is_memory_error, optimize,
                          plan_peak_bytes, run_morsels, scan)
from repro.engine import membudget as MB
from repro.engine import physical as P
from repro.engine.executor import run as xrun
from repro.obs import metrics
from repro.resilience import faults


def canon(table, count):
    n = int(count)
    cols = sorted(table.column_names)
    mats = [np.asarray(table[c])[:n] for c in cols]
    return tuple(cols), sorted(zip(*[m.tolist() for m in mats]))


def make_join_tables(n_r=400, n_s=1600, seed=3):
    R, S = relgen.generate(relgen.JoinWorkload("t", n_r, n_s, 2, 2,
                                               seed=seed))
    return {"R": R, "S": S}


# ---------------------------------------------------------------------------
# budget ledger
# ---------------------------------------------------------------------------
def test_budget_ledger_never_overcommits():
    b = MemoryBudget(100)
    assert b.try_reserve("a", 60)
    assert not b.try_reserve("b", 50)  # 60 + 50 > 100: refused, untouched
    assert b.reserved == 60 and b.available() == 40
    # re-reserving a live tag REPLACES its ticket (idempotent tags)
    assert b.try_reserve("a", 70)
    assert b.reserved == 70
    assert b.release("a") == 70
    assert b.release("a") == 0  # unknown-tag release is a safe no-op
    assert b.reserved == 0
    assert b.peak_reserved == 70  # high-water mark survives releases


def test_budget_rejects_nonpositive_total():
    with pytest.raises(ValueError):
        MemoryBudget(0)


def test_env_override_read_time_validation(monkeypatch):
    monkeypatch.setenv(MB.ENV_VAR, "123456")
    assert detect_budget_bytes() == 123456
    # validated at READ time, every call — like REPRO_PALLAS_INTERPRET
    monkeypatch.setenv(MB.ENV_VAR, "lots")
    with pytest.raises(ValueError, match="allowed"):
        detect_budget_bytes()
    monkeypatch.setenv(MB.ENV_VAR, "-5")
    with pytest.raises(ValueError):
        detect_budget_bytes()
    monkeypatch.delenv(MB.ENV_VAR)
    assert detect_budget_bytes() > 0


def test_is_memory_error_classifier():
    assert is_memory_error(MemoryError("boom"))
    assert is_memory_error(MemoryBudgetExceeded(10, 5))
    assert is_memory_error(RuntimeError("RESOURCE_EXHAUSTED: alloc failed"))
    assert is_memory_error(RuntimeError("Failed to allocate 1GB"))
    assert not is_memory_error(ValueError("bad shape"))


def test_memory_budget_exceeded_is_typed():
    e = MemoryBudgetExceeded(1000, 500, "unsplittable")
    assert isinstance(e, MemoryError)
    assert e.need_bytes == 1000 and e.budget_bytes == 500
    assert "1000" in str(e) and "unsplittable" in str(e)


# ---------------------------------------------------------------------------
# oom: fault family
# ---------------------------------------------------------------------------
def test_oom_fault_grammar_and_type():
    before = metrics.counter("resilience.oom_injected").value
    with faults.inject("oom:executor.run@0"):
        with pytest.raises(faults.OOMInjected) as ei:
            faults.check_oom("executor.run")
        assert isinstance(ei.value, MemoryError)  # routes onto morsel rung
        faults.check_oom("executor.run")  # occurrence 1: no re-fire
        faults.check_oom("qserve.admit")  # other site: never fires
    assert metrics.counter("resilience.oom_injected").value == before + 1


def test_oom_wildcard_site_rejected():
    with pytest.raises(ValueError):
        with faults.inject("oom:*"):
            pass


# ---------------------------------------------------------------------------
# morsel axis + out-of-core driver
# ---------------------------------------------------------------------------
def test_morsel_axis_selection():
    tables = make_join_tables()
    cat = Catalog(tables)
    join = optimize(scan("S").join(scan("R"), key="k"), cat,
                    measure_profile=False)
    assert P.morsel_axis(join.root) == "S"  # probe side splits
    gb = optimize(scan("S").group_by("k", s1="sum"), cat,
                  measure_profile=False)
    assert P.morsel_axis(gb.root) == "S"
    topk = optimize(scan("S").order_by("s1", limit=8), cat,
                    measure_profile=False)
    assert P.morsel_axis(topk.root) is None  # top-k is not splittable


def test_morsel_rows_pow2_lane_rounded():
    assert P.morsel_rows(2048, 2) == 1024
    assert P.morsel_rows(2048, 32) == 64
    assert P.morsel_rows(2048, 4096) == 64  # never below one tile
    assert P.morsel_rows(100, 2) == 64      # lane-rounded up


def test_run_morsels_join_bit_identical():
    tables = make_join_tables()
    plan = optimize(scan("S").join(scan("R"), key="k"), Catalog(tables),
                    measure_profile=False)
    whole = canon(*xrun(plan))
    before = metrics.counter("engine.morsel_runs").value
    for f in (2, 4, 8):
        assert canon(*run_morsels(plan, factor=f)) == whole
    assert metrics.counter("engine.morsel_runs").value > before


def test_run_morsels_unsplittable_raises():
    tables = make_join_tables()
    plan = optimize(scan("S").order_by("s1", limit=8), Catalog(tables),
                    measure_profile=False)
    with pytest.raises(ValueError):
        run_morsels(plan, factor=2)


def test_oom_fault_degrades_onto_morsel_rung():
    tables = make_join_tables()
    q = scan("S").join(scan("R"), key="k").group_by("k", s1="sum")
    oracle = canon(*xrun(optimize(q, Catalog(tables),
                                  measure_profile=False)))
    plan = optimize(q, Catalog(tables), measure_profile=False)
    with faults.inject("oom:executor.run@0"):
        got = canon(*xrun(plan))
    assert got == oracle
    assert plan.degraded_plan is not None
    assert plan.degraded_plan.morsel_factor == 2  # morsel rung, not 2x cap


def test_plan_peak_bytes_positive_and_counts_invariant():
    tables = make_join_tables()
    plan = optimize(scan("S").join(scan("R"), key="k"), Catalog(tables),
                    measure_profile=False)
    peak = plan_peak_bytes(plan)
    assert peak > 0
    counts = {n: t.num_rows for n, t in tables.items()}
    assert plan_peak_bytes(plan, tables, counts=counts) > 0


def test_plan_audit_and_answers_with_the_clustered_gather_kernel(monkeypatch):
    """A PHJ-OM plan whose output gathers run the windowed kernel (as on a
    backend that compiles Pallas): the peak-bytes audit walks the kernel's
    DMA semaphore, and the answers equal the XLA arm's."""
    from repro.kernels import ops

    tables = make_join_tables()
    plan = optimize(scan("S").join(scan("R"), key="k"), Catalog(tables),
                    measure_profile=False, force_join=("phj", "gftr"))
    oracle = canon(*xrun(plan))
    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    plan = optimize(scan("S").join(scan("R"), key="k"), Catalog(tables),
                    measure_profile=False, force_join=("phj", "gftr"))
    assert plan_peak_bytes(plan) > 0
    assert canon(*xrun(plan)) == oracle


# ---------------------------------------------------------------------------
# morsel-split group-by: bit identity across every strategy (property)
# ---------------------------------------------------------------------------
GB_STRATEGIES = ("sort", "partition", "partition_hash", "scatter",
                 "sort_pallas")


def _force_strategy(plan, strategy):
    root = dataclasses.replace(plan.root, strategy=strategy)
    return dataclasses.replace(plan, root=root, morsel_plans={})


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       n=st.sampled_from([65, 150]),
       shape=st.sampled_from(["uniform", "one_group", "boundary"]))
def test_morsel_groupby_bit_identical_all_strategies(seed, n, shape):
    """Chunked group-by (partial aggregates re-reduced, mean via
    sum+count) must be BIT-identical to the whole-relation run for every
    strategy, at even and uneven-tail widths, including the hostile
    all-rows-one-group and capacity-boundary key shapes."""
    rng = np.random.default_rng(seed)
    if shape == "one_group":
        keys = np.full(n, 3, np.int32)
    elif shape == "boundary":
        keys = rng.choice(np.array([0, 1, 62, 63], np.int32), n)
    else:
        keys = rng.integers(0, 64, n).astype(np.int32)
    t = Table({"k": jnp.asarray(keys),
               "v": jnp.asarray(rng.integers(0, 1000, n).astype(np.int32)),
               "w": jnp.asarray(rng.integers(0, 1000, n).astype(np.int32))})
    cat = Catalog({"S": t})
    q = scan("S").group_by("k", v="sum", w="mean")
    for strategy in GB_STRATEGIES:
        plan = _force_strategy(optimize(q, cat, measure_profile=False),
                               strategy)
        whole = canon(*xrun(plan))
        # factor 2 gives width >= n/2; larger factors clamp to the 64-row
        # tile floor, leaving zero-count tail morsels (skip path)
        for factor in (2, 4):
            got = canon(*run_morsels(plan, factor=factor))
            assert got == whole, (strategy, factor, shape)


# ---------------------------------------------------------------------------
# §4.4 memory-model ledger (GFTR vs GFUR) in explain()
# ---------------------------------------------------------------------------
def test_gftr_peak_never_above_gfur():
    # the paper's modeled conclusion: for any transform scratch >= one
    # column, GFTR's phase peak is <= GFUR's (strict once mt > mc)
    for mt in (1.0, 1.5, 2.0, 4.0):
        assert (memmodel.peak_memory("gftr", mt=mt)
                <= memmodel.peak_memory("gfur", mt=mt))
    assert (memmodel.peak_memory("gftr", mt=2.0)
            < memmodel.peak_memory("gfur", mt=2.0))
    # audited: the same join forced onto each pattern — GFTR may not peak
    # higher than GFUR (XLA fuses the transforms, so equality is common)
    tables = make_join_tables()
    q = scan("S").join(scan("R"), key="k")
    peaks = {}
    for pat in ("gftr", "gfur"):
        plan = optimize(q, Catalog(tables), measure_profile=False,
                        force_join=("phj", pat))
        peaks[pat] = plan_peak_bytes(plan)
    assert peaks["gftr"] <= peaks["gfur"]


def test_explain_renders_memory_ledger():
    tables = make_join_tables()
    plan = optimize(scan("S").join(scan("R"), key="k"), Catalog(tables),
                    measure_profile=False)
    text = plan.explain()
    assert "mem: model[gftr=" in text
    assert "gfur=" in text and "pattern=" in text
