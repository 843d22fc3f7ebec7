"""Resilience subsystem tests (DESIGN.md §13): escalation ladders,
deterministic fault injection, hostile inputs, and graceful degradation at
the kernel, executor, and serve layers.

Escalated knobs change row order (partition bits) and padded shape
(capacity), never the multiset of valid rows — results are compared as
canonicalized valid rows (sorted tuples over sorted columns)."""
from __future__ import annotations

import collections

from hypothesis import given, settings, strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import KEY_SENTINEL, Table, group_aggregate
from repro.core.groupby import groupby_partition_checked
from repro.core.groupjoin import groupjoin_checked, phj_groupjoin
from repro.core.hash_join import phj_join, phj_join_checked
from repro.kernels import ops as kops
from repro.obs import metrics
from repro.resilience import (EscalationExhausted, EscalationStep, Ladder,
                              escalation, faults)


def canon(table, count):
    """Valid rows, order/shape-insensitive (integer payloads only)."""
    n = int(count)
    cols = sorted(table.column_names)
    mats = [np.asarray(table[c])[:n] for c in cols]
    return tuple(cols), sorted(zip(*[m.tolist() for m in mats]))


def make_join_tables(rng, n_r=256, n_s=1024):
    R = Table({"k": jnp.asarray(rng.permutation(n_r).astype(np.int32)),
               "v": jnp.asarray(rng.integers(0, 99, n_r).astype(np.int32))})
    S = Table({"k": jnp.asarray(rng.integers(0, n_r, n_s).astype(np.int32)),
               "w": jnp.asarray(rng.integers(0, 9, n_s).astype(np.int32))})
    return R, S


# ---------------------------------------------------------------------------
# REPRO_FAULTS grammar: validated at read time, per call
# ---------------------------------------------------------------------------
def test_parse_accepts_full_grammar():
    plan = faults.parse("overflow:phj@0, pallas:*, raise:executor.run@1+3,"
                        "estimates:/16, seed:7")
    kinds = [s.kind for s in plan.specs]
    assert kinds == ["overflow", "pallas", "raise", "estimates", "seed"]
    assert plan.seed == 7
    assert plan.specs[0].when == frozenset({0})
    assert plan.specs[1].when is None  # every occurrence
    assert plan.specs[2].when == frozenset({1, 3})
    assert plan.specs[3].factor == pytest.approx(1 / 16)
    assert faults.parse("  ").specs == ()


@pytest.mark.parametrize("bad", [
    "overflow:phj",          # missing @<when>
    "overflow:@0",           # missing ladder name
    "pallas:",               # missing site
    "raise:*",               # wildcard raise is rejected
    "estimates:16",          # missing x|/ prefix
    "estimates:x0",          # factor must be > 0
    "estimates:xnope",
    "seed:abc",
    "overflow:phj@-1",       # negative occurrence
    "overflow:phj@one",
    "typo:phj@0",            # unknown kind
    "justaword",             # no ':'
])
def test_parse_rejects_bad_specs_naming_grammar(bad):
    with pytest.raises(ValueError) as exc:
        faults.parse(bad)
    msg = str(exc.value)
    assert faults.ENV_VAR in msg and "overflow:<ladder>@<when>" in msg


def test_env_var_validated_per_call_never_frozen(monkeypatch, rng):
    """The env var is (re)parsed at every injection-site call — setting a
    bad value AFTER import must raise, and fixing it must recover,
    matching the REPRO_PALLAS_INTERPRET read-time convention."""
    R, S = make_join_tables(rng)
    monkeypatch.setenv(faults.ENV_VAR, "overflow:nonsense")
    with pytest.raises(ValueError):
        phj_join_checked(R, S, key="k")
    monkeypatch.setenv(faults.ENV_VAR, "overflow:phj@0")
    out, rep = phj_join_checked(R, S, key="k", with_report=True)
    assert rep.escalated and rep.converged
    monkeypatch.delenv(faults.ENV_VAR)
    _, rep2 = phj_join_checked(R, S, key="k", with_report=True)
    assert not rep2.escalated


def test_inject_context_wins_over_env(monkeypatch):
    monkeypatch.setenv(faults.ENV_VAR, "overflow:phj@all")
    with faults.inject(""):
        assert not faults.overflow_forced("phj", 0)
    assert faults.overflow_forced("phj", 0)


def test_occurrence_counters_reset_per_activation():
    with faults.inject("pallas:somesite@0"):
        with pytest.raises(faults.FaultInjected):
            faults.check_pallas("somesite")
        faults.check_pallas("somesite")  # occurrence 1: not armed
    with faults.inject("pallas:somesite@0"):
        with pytest.raises(faults.FaultInjected):  # counters restarted
            faults.check_pallas("somesite")


# ---------------------------------------------------------------------------
# escalation ladder unit behavior
# ---------------------------------------------------------------------------
def _toy_ladder(max_attempts=8, cap_max_times=4):
    return Ladder("toy", [
        EscalationStep("cap", lambda kn, d: {**kn, "cap": kn["cap"] * 2},
                       max_times=cap_max_times),
        EscalationStep("fallback", lambda kn, d: {**kn, "exact": True},
                       max_times=1),
    ], max_attempts=max_attempts)


def test_ladder_converges_with_report():
    def check(kn):
        ok = bool(kn["cap"] >= 100 or kn.get("exact"))
        return ok, "" if ok else f"cap {kn['cap']} < 100", None

    rep = _toy_ladder().resolve({"cap": 16}, check)
    assert rep.converged and rep.escalated
    assert rep.final_knobs["cap"] == 128
    assert rep.steps_applied == {"cap": 3}
    assert [a.ok for a in rep.attempts] == [False, False, False, True]
    assert "converged" in rep.summary()


def test_ladder_rung_yields_to_next():
    """A rung returning None passes the attempt to the next rung instead
    of burning it."""
    def check(kn):
        return bool(kn.get("exact")), "needs exact", None

    rep = Ladder("toy", [
        EscalationStep("useless", lambda kn, d: None),
        EscalationStep("fallback", lambda kn, d: {**kn, "exact": True},
                       max_times=1),
    ]).resolve({"cap": 1}, check)
    assert rep.converged and rep.steps_applied == {"fallback": 1}


def test_ladder_exhaustion_is_typed_and_carries_report():
    def never_ok(kn):
        return False, "hopeless", None

    before = metrics.counter("resilience.ladder_exhausted").value
    with pytest.raises(EscalationExhausted) as exc:
        _toy_ladder(max_attempts=3).resolve({"cap": 1}, never_ok)
    rep = exc.value.report
    assert not rep.converged and len(rep.attempts) == 3
    assert "EXHAUSTED" in rep.summary()
    assert metrics.counter("resilience.ladder_exhausted").value == before + 1


def test_escalation_feeds_metrics():
    def check(kn):
        return kn["cap"] >= 2, "", None

    before = metrics.counter("core.overflow_escalations").value
    _toy_ladder().resolve({"cap": 1}, check)
    assert metrics.counter("core.overflow_escalations").value == before + 1


# ---------------------------------------------------------------------------
# the three production ladders: natural / forced / exhausted
# ---------------------------------------------------------------------------
def test_phj_ladder_forced_overflow_matches_oracle(rng):
    R, S = make_join_tables(rng)
    oracle = canon(*phj_join_checked(R, S, key="k"))
    with faults.inject("overflow:phj@0"):
        out, rep = phj_join_checked(R, S, key="k", with_report=True)
    assert rep.escalated and rep.converged and rep.wasted_checks == 1
    assert canon(*out) == oracle
    with pytest.raises(EscalationExhausted):
        with faults.inject("overflow:phj@all"):
            phj_join_checked(R, S, key="k")


def test_phj_ladder_smj_fallback_on_unsplittable_skew(rng):
    """One key's duplicates co-hash at any fan-out: bits cannot help, the
    ladder must fall through to sort-merge and still be exact."""
    R = Table({"k": jnp.asarray(np.zeros(600, np.int32)),
               "v": jnp.asarray(np.arange(600, dtype=np.int32))})
    S = Table({"k": jnp.asarray(np.zeros(50, np.int32)),
               "w": jnp.asarray(np.arange(50, dtype=np.int32))})
    out, rep = phj_join_checked(R, S, key="k", mode="mn",
                                out_size=600 * 50, with_report=True)
    assert rep.converged and rep.final_knobs["algorithm"] == "smj"
    assert int(out[1]) == 600 * 50


def test_groupjoin_ladder_grows_capacity_to_required(rng):
    R, S = make_join_tables(rng)
    kw = dict(key="k", group_key="k", aggs={"w": "sum"}, num_groups=256)
    oracle = canon(*groupjoin_checked(R, S, **kw))
    # capacity 4x under-provisioned: the ladder must grow it, not the bits
    out, rep = groupjoin_checked(R, S, with_report=True,
                                 **{**kw, "num_groups": 64})
    assert rep.escalated and rep.final_knobs["num_groups"] >= 64
    assert canon(*out) == oracle
    with faults.inject("overflow:groupjoin@0"):
        out2, rep2 = groupjoin_checked(R, S, with_report=True, **kw)
    assert rep2.escalated and canon(*out2) == oracle


def test_groupby_partition_ladder_forced_and_exhausted(rng):
    S = Table({"k": jnp.asarray(rng.integers(0, 256, 1024).astype(np.int32)),
               "w": jnp.asarray(rng.integers(0, 9, 1024).astype(np.int32))})
    kw = dict(key="k", aggs={"w": "sum"}, num_groups=256)
    oracle = canon(*groupby_partition_checked(S, **kw))
    with faults.inject("overflow:groupby_partition@0"):
        out, rep = groupby_partition_checked(S, with_report=True, **kw)
    assert rep.escalated and canon(*out) == oracle
    with pytest.raises(EscalationExhausted):
        with faults.inject("overflow:groupby_partition@all"):
            groupby_partition_checked(S, **kw)


# ---------------------------------------------------------------------------
# property: ladders converge under adversarially corrupted estimates
# ---------------------------------------------------------------------------
@settings(max_examples=8, deadline=None)
@given(factor=st.sampled_from([2, 4, 16, 64]), seed=st.integers(0, 10))
def test_ladders_converge_under_underestimates(factor, seed):
    """Distinct-count under-estimated up to 64x: every ladder must reach a
    fitting geometry within its attempt cap (growing bits/capacity/block,
    or falling back to an exact strategy) and match the oracle."""
    rng = np.random.default_rng(seed)
    n_r, n_s = 512, 1024
    R, S = make_join_tables(rng, n_r, n_s)

    # phj: partition bits chosen as if R had n_r/factor rows
    from repro.core.hash_join import choose_partition_bits
    bad_bits = choose_partition_bits(max(n_r // factor, 1), 64)
    oracle = canon(*phj_join_checked(R, S, key="k"))
    out, rep = phj_join_checked(R, S, key="k", build_block=64,
                                partition_bits=bad_bits, with_report=True)
    assert rep.converged and canon(*out) == oracle

    # groupjoin: accumulator capacity under-provisioned by `factor`
    kw = dict(key="k", group_key="k", aggs={"w": "sum"})
    oracle = canon(*groupjoin_checked(R, S, num_groups=n_r, **kw))
    out, rep = groupjoin_checked(R, S, num_groups=max(n_r // factor, 1),
                                 with_report=True, **kw)
    assert rep.converged and canon(*out) == oracle

    # groupby_partition: row block sized as if partitions were `factor`x
    # lighter
    gkw = dict(key="k", aggs={"w": "sum"}, num_groups=n_r)
    oracle = canon(*groupby_partition_checked(S, **gkw))
    out, rep = groupby_partition_checked(
        S, row_block=max(128 // factor, 8), partition_bits=0,
        with_report=True, **gkw)
    assert rep.converged and canon(*out) == oracle


# ---------------------------------------------------------------------------
# zero-overhead contract: disabled faults contribute nothing to the jaxpr
# ---------------------------------------------------------------------------
def test_fault_hooks_are_jaxpr_invisible(monkeypatch, rng):
    """With no faults active, tracing through the injection sites must
    yield the exact jaxpr of a build with every hook compiled out — the
    hooks are host-side and contribute nothing to the graph."""
    assert not faults.active()
    R, S = make_join_tables(rng, 128, 256)
    G = Table({"k": jnp.asarray(rng.integers(0, 32, 256).astype(np.int32)),
               "w": jnp.asarray(rng.integers(0, 9, 256).astype(np.int32))})

    def ops():
        j = phj_join(R, S, key="k", out_size=256)
        g = group_aggregate(G, key="k", aggs={"w": "sum"}, num_groups=64,
                            strategy="partition")
        return j[1] + g[1]

    base = str(jax.make_jaxpr(ops)())
    monkeypatch.setattr(faults, "active", lambda: False)
    monkeypatch.setattr(faults, "check_pallas", lambda site: None)
    monkeypatch.setattr(faults, "check_site", lambda site: None)
    monkeypatch.setattr(faults, "overflow_forced", lambda *a: False)
    monkeypatch.setattr(faults, "estimate_factor", lambda site="": 1.0)
    assert str(jax.make_jaxpr(ops)()) == base


# ---------------------------------------------------------------------------
# pallas -> xla degradation: every kernels/ops.py dispatch
# ---------------------------------------------------------------------------
def _site_cases(rng):
    digits = jnp.asarray(rng.integers(0, 16, 2048).astype(np.int32))
    keys = jnp.asarray(rng.integers(0, 1 << 20, 2048).astype(np.int32))
    build = jnp.sort(jnp.asarray(
        rng.choice(1 << 16, 1024, replace=False).astype(np.int32)))
    probe = jnp.sort(jnp.asarray(
        rng.integers(0, 1 << 16, 2048).astype(np.int32)))
    src = jnp.asarray(rng.integers(0, 99, 4096).astype(np.int32))
    # clustered, monotone indices: one window per tile of the kernel
    idx = jnp.repeat(jnp.arange(1024, dtype=jnp.int32) * 2, 2)
    skeys = jnp.sort(jnp.asarray(rng.integers(0, 64, 2048).astype(np.int32)))
    vals = jnp.asarray(rng.random(2048).astype(np.float32))
    return {
        "histogram": lambda: kops.histogram(digits, 16, "pallas"),
        "partition_ranks": lambda: kops.partition_ranks(digits, 16, "pallas"),
        "partition_plan": lambda: kops.partition_plan(digits, 16,
                                                      impl="pallas"),
        "sort_plan": lambda: kops.sort_plan(keys, "radix"),
        "merge_lower_bound": lambda: kops.merge_lower_bound(build, probe,
                                                            "pallas"),
        "clustered_gather": lambda: kops.clustered_gather(src, idx, "pallas"),
        "groupby_sorted_sum": lambda: kops.groupby_sorted_sum(skeys, vals,
                                                              64, "pallas"),
    }


@pytest.mark.parametrize("site", [
    "histogram", "partition_ranks", "partition_plan", "sort_plan",
    "merge_lower_bound", "clustered_gather", "groupby_sorted_sum",
])
def test_pallas_arm_failure_degrades_to_identical_xla(site, rng):
    fn = _site_cases(rng)[site]
    oracle = jax.tree_util.tree_map(np.asarray, fn())
    before = metrics.counter(f"resilience.kernel_fallbacks.{site}").value
    with faults.inject(f"pallas:{site}"):
        got = jax.tree_util.tree_map(np.asarray, fn())
    for a, b in zip(jax.tree_util.tree_leaves(oracle),
                    jax.tree_util.tree_leaves(got)):
        np.testing.assert_array_equal(a, b)
    assert metrics.counter(
        f"resilience.kernel_fallbacks.{site}").value > before


def test_hash_probe_and_groupjoin_probe_agg_degrade(rng):
    """The two fused probe kernels, driven through their operators."""
    R, S = make_join_tables(rng)
    oracle = canon(*phj_join(R, S, key="k", out_size=2048,
                             probe_impl="pallas"))
    with faults.inject("pallas:hash_probe"):
        got = canon(*phj_join(R, S, key="k", out_size=2048,
                              probe_impl="pallas"))
    assert got == oracle

    kw = dict(key="k", group_key="k", aggs={"w": "sum"}, num_groups=256)
    oracle = canon(*phj_groupjoin(R, S, probe_impl="pallas", **kw))
    with faults.inject("pallas:groupjoin_probe_agg"):
        got = canon(*phj_groupjoin(R, S, probe_impl="pallas", **kw))
    assert got == oracle


# ---------------------------------------------------------------------------
# hostile inputs: sentinel-colliding keys, empty relations, one group
# ---------------------------------------------------------------------------
GB_STRATEGIES = ("sort", "partition", "partition_hash", "scatter",
                 "sort_pallas")


def _gb_oracle(keys, vals):
    acc = collections.defaultdict(int)
    for k, v in zip(keys.tolist(), vals.tolist()):
        if k != KEY_SENTINEL:
            acc[k] += v
    return sorted((k, s) for k, s in acc.items())


def _gb_rows(out):
    (t, c) = out
    n = int(c)
    ks = np.asarray(t["k"])[:n]
    ss = np.asarray(t["v_sum"])[:n]
    return sorted((int(k), int(s)) for k, s in zip(ks, ss)
                  if k != KEY_SENTINEL)


@pytest.mark.parametrize("strategy", GB_STRATEGIES)
def test_groupby_sentinel_colliding_keys(strategy, rng):
    """Rows whose key equals the padding sentinel must be dropped exactly
    — never aggregated, never corrupting neighbors."""
    keys = rng.integers(0, 32, 512).astype(np.int32)
    keys[::7] = KEY_SENTINEL
    vals = rng.integers(0, 99, 512).astype(np.int32)
    T = Table({"k": jnp.asarray(keys), "v": jnp.asarray(vals)})
    out = group_aggregate(T, key="k", aggs={"v": "sum"}, num_groups=64,
                          strategy=strategy)
    assert _gb_rows(out) == _gb_oracle(keys, vals)


@pytest.mark.parametrize("strategy", GB_STRATEGIES)
def test_groupby_empty_relation(strategy):
    T = Table({"k": jnp.zeros((0,), jnp.int32),
               "v": jnp.zeros((0,), jnp.int32)})
    t, c = group_aggregate(T, key="k", aggs={"v": "sum"}, num_groups=16,
                           strategy=strategy)
    assert int(c) == 0


@pytest.mark.parametrize("strategy", GB_STRATEGIES)
def test_groupby_all_rows_one_group(strategy, rng):
    """Maximal skew: every row in one group. The static-shape partition
    strategy cannot adapt inside jit — its overflow must be *detectable*
    and its resilient entry point (the checked ladder) exact; every other
    strategy must be exact as-is."""
    vals = rng.integers(0, 99, 1024).astype(np.int32)
    T = Table({"k": jnp.full((1024,), 3, jnp.int32), "v": jnp.asarray(vals)})
    expected = [(3, int(vals.sum()))]
    if strategy == "partition":
        from repro.core.groupby import groupby_partition_overflowed

        over, _, mx = groupby_partition_overflowed(T["k"])
        assert over and int(mx) == 1024  # never silent
        t, c = groupby_partition_checked(T, key="k", aggs={"v": "sum"},
                                         num_groups=16)
    else:
        t, c = group_aggregate(T, key="k", aggs={"v": "sum"}, num_groups=16,
                               strategy=strategy)
    assert _gb_rows((t, c)) == expected


def test_phj_sentinel_colliding_keys(rng):
    """Sentinel keys on either side must not match anything — including
    each other — and must not perturb real matches (they are isolated in
    their own partition, never co-resident with real keys)."""
    R, S = make_join_tables(rng, 128, 512)
    rk = np.asarray(R["k"]).copy()
    rk[::5] = KEY_SENTINEL
    sk = np.asarray(S["k"]).copy()
    sk[::3] = KEY_SENTINEL
    Rh = Table({"k": jnp.asarray(rk), "v": R["v"]})
    Sh = Table({"k": jnp.asarray(sk), "w": S["w"]})
    out, count = phj_join_checked(Rh, Sh, key="k", out_size=1024)
    rmap = {int(k): int(v) for k, v in zip(rk, np.asarray(R["v"]))
            if k != KEY_SENTINEL}
    oracle = sorted((int(k), rmap[int(k)], int(w))
                    for k, w in zip(sk, np.asarray(S["w"]))
                    if int(k) in rmap)
    got = sorted(zip(*[np.asarray(out[c])[:int(count)].tolist()
                       for c in ("k", "v", "w")]))
    assert got == oracle


def test_phj_empty_relations(rng):
    R, S = make_join_tables(rng, 64, 128)
    empty_r = Table({"k": jnp.zeros((0,), jnp.int32),
                     "v": jnp.zeros((0,), jnp.int32)})
    empty_s = Table({"k": jnp.zeros((0,), jnp.int32),
                     "w": jnp.zeros((0,), jnp.int32)})
    for a, b in ((empty_r, S), (R, empty_s), (empty_r, empty_s)):
        out, count = phj_join_checked(a, b, key="k", out_size=128)
        assert int(count) == 0


def test_phj_all_probes_one_key(rng):
    """Every probe row hits one build key: maximal partition skew on the
    probe side."""
    R, S = make_join_tables(rng, 128, 512)
    Sh = Table({"k": jnp.full((512,), 7, jnp.int32), "w": S["w"]})
    out, count = phj_join_checked(R, Sh, key="k", out_size=512)
    assert int(count) == 512
    assert set(np.asarray(out["k"])[:512].tolist()) == {7}


def test_groupjoin_empty_relations(rng):
    R, S = make_join_tables(rng, 64, 128)
    empty_s = Table({"k": jnp.zeros((0,), jnp.int32),
                     "w": jnp.zeros((0,), jnp.int32)})
    t, c = groupjoin_checked(R, empty_s, key="k", group_key="k",
                             aggs={"w": "sum"}, num_groups=64)
    assert int(c) == 0


# ---------------------------------------------------------------------------
# estimate corruption (stats layer)
# ---------------------------------------------------------------------------
def test_estimate_factor_unseeded_is_exact():
    with faults.inject("estimates:/8"):
        assert faults.estimate_factor("distinct") == pytest.approx(1 / 8)
    assert faults.estimate_factor("distinct") == 1.0


def test_estimate_factor_seeded_is_deterministic_and_bounded():
    with faults.inject("estimates:/8,seed:3"):
        a = faults.estimate_factor("distinct")
        b = faults.estimate_factor("distinct")
        other = faults.estimate_factor("rows")
    assert a == b
    assert 1 / 16 <= a <= 1 / 4  # log2 jitter within [f/2, f*2]
    assert other != a


def test_stats_distinct_estimate_corrupted(rng):
    from repro.engine.stats import estimate_distinct

    col = jnp.asarray(rng.permutation(4096).astype(np.int32))
    clean = estimate_distinct(col)
    with faults.inject("estimates:/4"):
        corrupt = estimate_distinct(col)
    assert corrupt == pytest.approx(clean / 4, rel=0.26)


# ---------------------------------------------------------------------------
# executor: degrade-once re-plan
# ---------------------------------------------------------------------------
def _star_plan():
    from repro.data import relgen
    from repro.engine import Catalog, optimize, scan

    w = relgen.JoinWorkload("t", 500, 2000, 2, 1, match_ratio=1.0)
    R, S = relgen.generate(w)
    cat = Catalog({"R": R, "S": S})
    q = scan("R").join(scan("S"), key="k").group_by("k", s1="sum")
    return lambda: optimize(q, cat, measure_profile=False)


def test_executor_degrades_once_and_matches(rng):
    mk = _star_plan()
    oracle = canon(*mk().run())
    plan = mk()
    before = metrics.counter("resilience.plan_degradations").value
    with faults.inject("raise:executor.run@0"):
        got = canon(*plan.run())
    assert got == oracle
    assert plan.degraded_plan is not None
    assert plan.degraded_plan.degraded.startswith("DEGRADED[")
    assert "DEGRADED[" in plan.degraded_plan.explain()
    assert metrics.counter("resilience.plan_degradations").value == before + 1


def test_executor_persistent_failure_reraises():
    plan = _star_plan()()
    with pytest.raises(faults.FaultInjected):
        with faults.inject("raise:executor.run@all"):
            plan.run()


def test_executor_programming_errors_not_degraded(monkeypatch):
    from repro.engine import executor

    plan = _star_plan()()
    def boom(node, tables):
        raise TypeError("a bug, not an overflow")
    monkeypatch.setattr(executor, "execute", boom)
    with pytest.raises(TypeError):
        plan.run(jit=False)
    assert plan.degraded_plan is None


def test_degrade_plan_transforms_structure():
    from repro.engine import physical as P

    plan = _star_plan()()
    deg = P.degrade_plan(plan, "test-reason")
    assert deg.degraded == "DEGRADED[test-reason]"

    def walk(a, b):
        if isinstance(b, (P.PJoin, P.PGroupBy, P.PGroupJoin, P.PFilter)):
            assert b.capacity >= 2 * a.capacity
        if isinstance(b, P.PGroupBy):
            assert b.strategy == "sort"
        if isinstance(b, P.PGroupJoin):
            assert b.agg_strategy == "sort"
        if isinstance(b, P.PJoin):
            assert b.algorithm != "phj"
        if isinstance(b, P.POrderByLimit):
            assert b.capacity == a.capacity  # the limit IS the semantics
        for ka, kb in zip(a.children(), b.children()):
            walk(ka, kb)

    walk(plan.root, deg.root)


def test_trace_escalations_render_in_explain():
    plan = _star_plan()()
    with faults.inject("overflow:phj@0"):
        t, c, tr = plan.run(trace=True)
    assert tr.escalations and any(r.operator == "phj" for r in tr.escalations)
    txt = plan.explain(actuals=tr)
    assert "escalation: phj" in txt


# ---------------------------------------------------------------------------
# serve: poisoned-query isolation, shedding, deadlines
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def serve_setup():
    from repro.configs.base import get_reduced_config
    from repro.models import model as M

    cfg = get_reduced_config("olmo-1b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _engine(serve_setup, **kw):
    from repro.serve.engine import ServeEngine

    cfg, params = serve_setup
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_len", 32)
    kw.setdefault("eos_id", -1)
    kw.setdefault("retry_backoff_s", 0.0)
    return ServeEngine(cfg, params, **kw)


def test_serve_poisoned_query_fails_alone(serve_setup, rng):
    from repro.models import model as M
    from repro.serve.engine import Request

    cfg, params = serve_setup
    eng = _engine(serve_setup, step_retries=1)
    real = jax.jit(lambda p, c, t, pos: M.decode_step(cfg, p, c, t, pos))

    def step_fn(p, c, t, pos):
        if any(r is not None and r.rid == 2 for r in eng.slot_req):
            raise RuntimeError("poisoned query")
        return real(p, c, t, pos)

    eng._step = step_fn
    reqs = [Request(rid=i, max_tokens=4, retries_left=1,
                    prompt=rng.integers(3, cfg.vocab_size, 3).tolist())
            for i in range(4)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    assert reqs[2].done and reqs[2].error == "poisoned"
    for r in reqs:
        if r.rid != 2:
            assert r.done and r.error == "" and len(r.out) == 4


def test_serve_step_retry_recovers_transient(serve_setup, rng):
    """A step that fails once then succeeds is absorbed by the retry
    budget: no eviction, every request completes."""
    from repro.models import model as M
    from repro.serve.engine import Request

    cfg, params = serve_setup
    eng = _engine(serve_setup, step_retries=2)
    real = jax.jit(lambda p, c, t, pos: M.decode_step(cfg, p, c, t, pos))
    calls = {"n": 0}

    def flaky(p, c, t, pos):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("transient")
        return real(p, c, t, pos)

    eng._step = flaky
    before = metrics.counter("resilience.serve_retries").value
    r = Request(rid=0, max_tokens=3,
                prompt=rng.integers(3, cfg.vocab_size, 3).tolist())
    eng.submit(r)
    eng.run()
    assert r.done and r.error == "" and len(r.out) == 3
    assert metrics.counter("resilience.serve_retries").value == before + 1


def test_serve_load_shedding(serve_setup):
    from repro.serve.engine import Request

    eng = _engine(serve_setup, max_batch=1, max_queue=2)
    before = metrics.counter("resilience.serve_shed").value
    reqs = [Request(rid=i, prompt=[3, 4], max_tokens=2) for i in range(5)]
    for r in reqs:
        eng.submit(r)
    shed = [r for r in reqs if r.error == "shed"]
    assert len(shed) == 3 and all(r.done for r in shed)
    assert metrics.counter("resilience.serve_shed").value == before + 3
    eng.run()
    assert all(r.done for r in reqs)
    assert all(len(r.out) == 2 for r in reqs if r.error == "")


def test_serve_deadline_eviction(serve_setup):
    from repro.serve.engine import Request

    eng = _engine(serve_setup, max_batch=1)
    slow = Request(rid=0, prompt=[3, 4, 5], max_tokens=50, deadline_ticks=4)
    queued = Request(rid=1, prompt=[3, 4], max_tokens=2, deadline_ticks=2)
    eng.submit(slow)
    eng.submit(queued)
    eng.run()
    assert slow.done and slow.error == "deadline"
    # rid 1's deadline (tick 2) passed while it waited in the queue
    assert queued.done and queued.error == "deadline"


def test_serve_fault_site(serve_setup):
    from repro.serve.engine import Request

    eng = _engine(serve_setup, max_batch=1, step_retries=0)
    r = Request(rid=9, prompt=[3, 4], max_tokens=2, retries_left=0)
    eng.submit(r)
    with faults.inject("raise:serve.step@all"):
        eng.run()
    assert r.done and r.error == "poisoned"


# ---------------------------------------------------------------------------
# degradation events are observable
# ---------------------------------------------------------------------------
def test_degradations_recorded_in_ring(rng):
    since = escalation.current_seq()
    digits = jnp.asarray(rng.integers(0, 16, 512).astype(np.int32))
    with faults.inject("pallas:histogram"):
        kops.histogram(digits, 16, "pallas")
    events = escalation.recent_degradations(since)
    assert any(d["component"] == "kernels.histogram" for d in events)


def test_serve_deadline_expires_on_admission_tick(serve_setup):
    """A queued request whose deadline lands on the EXACT tick a slot
    frees up is evicted by the deadline sweep, not admitted: sweep runs
    before admission every tick."""
    from repro.serve.engine import Request

    def occupied_engine():
        eng = _engine(serve_setup, max_batch=1)
        eng.submit(Request(rid=0, prompt=[3, 4, 5], max_tokens=4))
        return eng

    # reference run: when would the victim be admitted?
    eng = occupied_engine()
    ref = Request(rid=1, prompt=[3, 4], max_tokens=2)
    eng.submit(ref)
    eng.run()
    assert ref.done and ref.error == ""
    admit_tick = ref.submit_tick + ref.ticks_queued

    # deadline == admission tick: the sweep must win the race
    eng = occupied_engine()
    victim = Request(rid=1, prompt=[3, 4], max_tokens=2,
                     deadline_ticks=admit_tick)
    eng.submit(victim)
    eng.run()
    assert victim.done and victim.error == "deadline"
    assert victim.out == [] and victim.done_tick == admit_tick

    # a deadline past its completion point and it runs untouched
    eng = occupied_engine()
    ok = Request(rid=1, prompt=[3, 4], max_tokens=2,
                 deadline_ticks=admit_tick + 10)
    eng.submit(ok)
    eng.run()
    assert ok.done and ok.error == "" and len(ok.out) == 2


def test_serve_requeued_request_reruns_full_prefill(serve_setup, rng):
    """A request evicted mid-decode and requeued must re-run its FULL
    prefill with cleared output: its final output equals a fresh engine's
    (no cache or output state leaks from the failed run)."""
    from repro.models import model as M
    from repro.serve.engine import Request

    cfg, params = serve_setup
    prompt = rng.integers(3, cfg.vocab_size, 3).tolist()

    eng_ref = _engine(serve_setup, max_batch=1)
    r_ref = Request(rid=0, prompt=list(prompt), max_tokens=4)
    eng_ref.submit(r_ref)
    eng_ref.run()
    assert r_ref.done and len(r_ref.out) == 4

    eng = _engine(serve_setup, max_batch=1, step_retries=0)
    real = jax.jit(lambda p, c, t, pos: M.decode_step(cfg, p, c, t, pos))
    calls = {"n": 0}

    def step_fn(p, c, t, pos):
        calls["n"] += 1
        if calls["n"] == 5:  # two decode outputs exist; then the step dies
            raise RuntimeError("mid-decode fault")
        return real(p, c, t, pos)

    eng._step = step_fn
    r = Request(rid=1, prompt=list(prompt), max_tokens=4, retries_left=1)
    eng.submit(r)
    eng.run()
    assert r.done and r.error == "" and r.retries_left == 0
    assert r.ticks_retrying >= 1
    assert r.out == r_ref.out, (r.out, r_ref.out)


def test_serve_latency_breakdown(serve_setup, rng):
    from repro.serve.engine import Request, ServeEngine

    cfg, _ = serve_setup
    eng = _engine(serve_setup, max_batch=1)
    reqs = [Request(rid=i, max_tokens=3,
                    prompt=rng.integers(3, cfg.vocab_size, 3).tolist())
            for i in range(3)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    for r in reqs:
        assert r.done and r.error == ""
        assert r.ticks_running > 0 and r.ticks_retrying == 0
        # ticks are conserved: queued + running spans submit..done
        assert r.ticks_queued + r.ticks_running == r.done_tick - r.submit_tick + 1
    # single slot: each successor queues at least as long as the last
    waits = [r.ticks_queued for r in reqs]
    assert waits == sorted(waits) and waits[-1] > waits[0]
    summary = ServeEngine.latency_summary()
    for stage in ("ticks_queued", "ticks_running", "ticks_retrying"):
        assert summary[stage]["count"] >= 3
        assert {"p50", "p95", "p99"} <= set(summary[stage])
