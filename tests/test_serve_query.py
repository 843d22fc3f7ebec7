"""Query-serving runtime: bucketing, signatures, admission, breakers,
saturation recovery, and the chaos harness (DESIGN.md §14)."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Table
from repro.data import relgen
from repro.engine import Catalog, optimize, scan
from repro.obs import metrics
from repro.serve import query as Q


def canon(table, count):
    n = int(count)
    cols = sorted(table.column_names)
    mats = [np.asarray(table[c])[:n] for c in cols]
    return tuple(cols), sorted(zip(*[m.tolist() for m in mats]))


def make_join_tables(n_r, n_s, seed=0):
    R, S = relgen.generate(relgen.JoinWorkload("t", n_r, n_s, 1, 1,
                                               seed=seed))
    return {"R": R, "S": S}


def one_shot(plan, tables):
    return canon(*optimize(plan, Catalog(tables),
                           measure_profile=False).run())


JOIN_PLAN = scan("S").join(scan("R"), key="k")


# ---------------------------------------------------------------------------
# bucketing / padding / signatures
# ---------------------------------------------------------------------------
def test_bucket_rows_power_of_two_floor():
    assert Q.bucket_rows(0) == Q.MIN_BUCKET
    assert Q.bucket_rows(1) == Q.MIN_BUCKET
    assert Q.bucket_rows(64) == 64
    assert Q.bucket_rows(65) == 128
    assert Q.bucket_rows(1500) == 2048
    assert Q.bucket_rows(2048) == 2048


def test_pad_table_preserves_uniqueness_and_wraps_floats():
    t = Table({"k": jnp.asarray(np.array([5, 3, 9], np.int32)),
               "x": jnp.asarray(np.array([1.5, 2.5, 3.5], np.float32))})
    p = Q.pad_table(t, 8)
    assert p.num_rows == 8
    k = np.asarray(p["k"])
    # original rows intact, integer padding continues past the max so the
    # column stays unique (PK-FK proofs survive padding)
    assert k[:3].tolist() == [5, 3, 9]
    assert len(set(k.tolist())) == 8
    assert k[3:].min() > 9
    assert np.asarray(p["x"])[:3].tolist() == [1.5, 2.5, 3.5]
    assert Q.pad_table(t, 3) is t
    with pytest.raises(ValueError):
        Q.pad_table(t, 2)


def test_plan_signature_buckets_collapse_sizes():
    t1 = make_join_tables(400, 1500, seed=1)
    t2 = make_join_tables(450, 1200, seed=2)  # same buckets (512, 2048)
    t3 = make_join_tables(400, 2500, seed=3)  # S in the next bucket
    s1, b1 = Q.plan_signature(JOIN_PLAN, t1)
    s2, _ = Q.plan_signature(JOIN_PLAN, t2)
    s3, _ = Q.plan_signature(JOIN_PLAN, t3)
    assert s1 == s2
    assert s1 != s3
    assert b1 == {"R": 512, "S": 2048}
    # the plan tree (filter constants included) is part of the identity
    f1 = scan("S").filter("s1", "<", 10).join(scan("R"), key="k")
    f2 = scan("S").filter("s1", "<", 11).join(scan("R"), key="k")
    assert Q.plan_signature(f1, t1)[0] != Q.plan_signature(f2, t1)[0]


def test_executor_counts_reuse_one_compiled_plan():
    """The bucketed executable (counts as traced scalars) serves multiple
    datasets padded to the same buckets, bit-identically to per-dataset
    one-shot runs — without touching the count-free compiled slot."""
    datasets = [make_join_tables(400, 1500, seed=4),
                make_join_tables(450, 1200, seed=5)]
    sig, buckets = Q.plan_signature(JOIN_PLAN, datasets[0])
    padded0 = {n: Q.pad_table(t, buckets[n]) for n, t in datasets[0].items()}
    plan = optimize(JOIN_PLAN, Catalog(padded0), measure_profile=False)
    for tb in datasets:
        padded = {n: Q.pad_table(t, buckets[n]) for n, t in tb.items()}
        counts = {n: t.num_rows for n, t in tb.items()}
        got = canon(*plan.run(padded, counts=counts))
        assert got == one_shot(JOIN_PLAN, tb)
    assert len(plan.compiled_bucketed) == 1  # one executable, both datasets
    assert plan.compiled is None  # the legacy slot never materialized


# ---------------------------------------------------------------------------
# server: fast path, cache sharing, admission control
# ---------------------------------------------------------------------------
def drive(server, reqs, per_tick=4, max_ticks=500):
    i = 0
    while (i < len(reqs) or server.queue
           or server.deferred) and server.tick < max_ticks:
        for _ in range(per_tick):
            if i < len(reqs):
                server.submit(reqs[i])
                i += 1
        server.step()


def test_server_shares_compiled_plan_across_sizes():
    sizes = [(400, 1500), (450, 1200), (300, 1700)]
    reqs = [Q.QueryRequest(qid=i, plan=JOIN_PLAN,
                           tables=make_join_tables(nr, ns, seed=10 + i))
            for i, (nr, ns) in enumerate(sizes)]
    before = metrics.counter("qserve.plans_compiled").value
    server = Q.QueryServer()
    drive(server, reqs)
    assert metrics.counter("qserve.plans_compiled").value == before + 1
    for req in reqs:
        assert req.done and not req.error and req.path == "fast"
        assert canon(*req.result) == one_shot(JOIN_PLAN, req.tables)
        assert req.signature == reqs[0].signature
        assert req.exec_wall_s > 0 and req.done_tick >= req.submit_tick


def test_server_admission_price_and_shedding():
    tb = make_join_tables(400, 1500, seed=20)
    priced = Q.QueryServer(max_price_s=0.0)
    req = Q.QueryRequest(qid=0, plan=JOIN_PLAN, tables=tb)
    priced.submit(req)
    priced.run()
    assert req.error == "rejected" and req.result is None

    shedder = Q.QueryServer(max_queue=2)
    reqs = [Q.QueryRequest(qid=i, plan=JOIN_PLAN, tables=tb)
            for i in range(5)]
    for r in reqs:
        shedder.submit(r)  # all before any tick: 2 queued, 3 shed
    assert [r.error for r in reqs] == ["", "", "shed", "shed", "shed"]
    shedder.run()
    assert all(not r.error for r in reqs[:2])


def test_server_hard_failure_fails_one_request_of_the_tick():
    """A failure no degraded plan can fix (here a plain RuntimeError, as a
    kernel that fails to compile raises) fails its own request loudly —
    no safe chain, no breaker — while the rest of the tick's batch runs
    and every bytes ticket is released."""
    tb = make_join_tables(400, 1500, seed=23)
    server = Q.QueryServer(slots_per_tick=2)
    bad = Q.QueryRequest(qid=0, plan=JOIN_PLAN, tables=tb)
    good = Q.QueryRequest(qid=1, plan=JOIN_PLAN, tables=tb)
    real_fast = server._run_fast

    def run_fast(entry, req):
        if req is bad:
            raise RuntimeError("kernel failed to compile")
        return real_fast(entry, req)

    server._run_fast = run_fast
    watched = ("qserve.hard_failures", "qserve.fast_failures",
               "qserve.safe_runs", "qserve.failed")
    before = {c: metrics.counter(c).value for c in watched}
    server.submit(bad)
    server.submit(good)
    server.step()
    assert bad.admit_tick == good.admit_tick == 1  # one tick, one batch
    assert bad.done and bad.error == "failed" and bad.result is None
    assert "RuntimeError: kernel failed to compile" in bad.detail
    assert good.done and not good.error and good.path == "fast"
    assert canon(*good.result) == one_shot(JOIN_PLAN, tb)
    moved = {c: metrics.counter(c).value - before[c] for c in watched}
    assert moved == {"qserve.hard_failures": 1, "qserve.fast_failures": 0,
                     "qserve.safe_runs": 0, "qserve.failed": 0}
    assert server.breakers[bad.signature].failures == 0
    assert server.budget.reserved == 0
    assert not server.queue and not server.deferred


def test_server_deadline_expires_on_admission_tick():
    """A queued query whose deadline lands exactly on the tick it would be
    admitted is evicted, not run: the deadline sweep precedes admission."""
    tb = make_join_tables(400, 1500, seed=21)
    server = Q.QueryServer(slots_per_tick=1)
    first = Q.QueryRequest(qid=0, plan=JOIN_PLAN, tables=tb)
    racer = Q.QueryRequest(qid=1, plan=JOIN_PLAN, tables=tb,
                           deadline_ticks=2)  # would be admitted at tick 2
    server.submit(first)
    server.submit(racer)
    server.run()
    assert first.done and not first.error
    assert racer.error == "deadline" and racer.result is None
    assert racer.done_tick == 2 and racer.admit_tick == -1


def test_server_tick_budget_paces_admission():
    tb = make_join_tables(400, 1500, seed=22)
    server = Q.QueryServer(slots_per_tick=4)
    probe = Q.QueryRequest(qid=0, plan=JOIN_PLAN, tables=tb)
    server.submit(probe)
    server.run()
    assert probe.done and probe.price_s > 0
    # budget covers exactly one query per tick: 3 queries take 3 ticks
    budget = Q.QueryServer(slots_per_tick=4,
                           tick_budget_s=probe.price_s * 1.5)
    reqs = [Q.QueryRequest(qid=i, plan=JOIN_PLAN, tables=tb)
            for i in range(3)]
    for r in reqs:
        budget.submit(r)
    budget.run()
    assert [r.admit_tick for r in reqs] == [1, 2, 3]
    assert all(not r.error for r in reqs)


# ---------------------------------------------------------------------------
# circuit breaker
# ---------------------------------------------------------------------------
def test_breaker_state_machine():
    br = Q.CircuitBreaker("sig", threshold=2, cooldown=3, max_cooldown=12)
    assert br.route(1) == "fast"
    br.record_fast_failure(1)
    assert br.state == Q.CLOSED  # one failure is not a pattern
    br.record_fast_failure(2)
    assert br.state == Q.OPEN
    assert br.route(3) == "safe"  # quarantined during cooldown
    assert br.route(5) == "fast" and br.state == Q.HALF_OPEN  # probe
    br.record_fast_failure(5)  # probe failed: reopen, cooldown doubles
    assert br.state == Q.OPEN and br.cooldown == 6
    assert br.route(7) == "safe"
    assert br.route(11) == "fast" and br.state == Q.HALF_OPEN
    br.record_fast_success(11)  # probe succeeded: close, cooldown resets
    assert br.state == Q.CLOSED and br.cooldown == 3
    assert br.route(12) == "fast"


def test_server_breaker_quarantines_and_recovers():
    """First two queries of a signature hard-fail -> breaker opens; while
    open, queries ride the safe path; a half-open probe after the fault
    clears closes it again. Results on every path match the oracle."""
    plan = scan("S").group_by("k", s1="sum")
    mk = lambda i: {"S": relgen.generate(  # noqa: E731
        relgen.JoinWorkload("t", 400, 1500, 1, 1, seed=40 + i))[1]}
    server = Q.QueryServer(breaker_cooldown=2)
    reqs = [Q.QueryRequest(qid=i, plan=plan, tables=mk(i),
                           fault_spec="raise:qserve.execute" if i < 2 else "")
            for i in range(8)]
    drive(server, reqs, per_tick=1)
    assert [r.qid for r in reqs if r.error] == [0, 1]
    paths = [r.path for r in reqs if not r.error]
    assert "safe" in paths  # quarantine actually ran
    assert paths[-1] == "fast"  # and the probe recovered the fast path
    br = server.breakers[reqs[0].signature]
    assert br.state == Q.CLOSED
    for r in reqs[2:]:
        assert canon(*r.result) == one_shot(plan, r.tables)


def test_server_saturation_escalates_to_correct_result():
    """estimates:/32 poisons the cached plan's capacities at planning time;
    saturation detection must catch the silent truncation and the safe
    path must escalate degrade levels until results match the oracle."""
    plan = scan("S").group_by("k", s1="sum")
    # sparse keys: domain 5000 >> distinct, so capacities hinge on the
    # (corrupted) distinct estimate
    mk = lambda i: {"S": relgen.generate(  # noqa: E731
        relgen.JoinWorkload("t", 5000, 1500, 1, 1, seed=50 + i))[1]}
    before = metrics.counter("qserve.saturations").value
    server = Q.QueryServer(breaker_cooldown=2)
    reqs = [Q.QueryRequest(qid=i, plan=plan, tables=mk(i),
                           fault_spec="estimates:/32") for i in range(4)]
    drive(server, reqs, per_tick=1)
    assert metrics.counter("qserve.saturations").value > before
    entry = server.cache[reqs[0].signature]
    assert entry.safe_level > 0  # converged level cached for the signature
    for r in reqs:
        assert r.done and not r.error, (r.qid, r.detail)
        assert canon(*r.result) == one_shot(plan, r.tables)


# ---------------------------------------------------------------------------
# memory governor (DESIGN.md §15): bytes tickets, deferral, morsel runs
# ---------------------------------------------------------------------------
def test_server_mem_rejects_unsplittable_with_typed_error():
    """A query that can NEVER fit the budget (top-k has no morsel axis)
    must be rejected with the typed error — not crash, not defer forever."""
    tables = {"S": relgen.generate(
        relgen.JoinWorkload("t", 5000, 1500, 1, 1, seed=9))[1]}
    plan = scan("S").filter("s1", "<", 1 << 30).order_by("s1", limit=32)
    before = metrics.counter("qserve.mem_rejections").value
    server = Q.QueryServer(measure_profile=False, mem_budget_bytes=4096)
    req = Q.QueryRequest(qid=0, plan=plan, tables=tables)
    server.submit(req)
    server.run()
    assert req.error == "rejected"
    assert "MemoryBudgetExceeded" in req.detail
    assert metrics.counter("qserve.mem_rejections").value == before + 1
    assert server.budget.reserved == 0


def test_server_chunked_run_bit_identical_under_tight_budget():
    """A splittable query whose whole-plan peak exceeds the budget must be
    served through the morsel driver, bit-identical to its oracle."""
    rng = np.random.default_rng(11)
    mk = lambda: {"B": Table(  # noqa: E731
        {f"c{c}": jnp.asarray(rng.integers(0, 100, 30_000).astype(np.int32))
         for c in range(16)})}
    plan = scan("B").filter("c0", "<", 60)
    t0 = mk()
    padded = {n: Q.pad_table(t, Q.bucket_rows(t.num_rows))
              for n, t in t0.items()}
    phys = optimize(plan, Catalog(padded), measure_profile=False)
    from repro.engine import plan_peak_bytes
    whole = plan_peak_bytes(phys, padded,
                            counts={n: t.num_rows for n, t in t0.items()})
    before = metrics.counter("qserve.chunked_runs").value
    server = Q.QueryServer(measure_profile=False,
                           mem_budget_bytes=int(whole * 0.6))
    reqs = [Q.QueryRequest(qid=i, plan=plan, tables=t0 if i == 0 else mk())
            for i in range(2)]
    drive(server, reqs, per_tick=1)
    for r in reqs:
        assert r.done and not r.error, (r.qid, r.detail)
        assert r.morsels >= 2
        assert canon(*r.result) == one_shot(plan, r.tables)
    entry = server.cache[reqs[0].signature]
    assert entry.morsel_factor >= 2  # sized ticket is the MORSEL peak
    assert entry.peak_bytes <= server.budget.total
    assert metrics.counter("qserve.chunked_runs").value == before + 2
    assert server.budget.reserved == 0
    assert server.budget.peak_reserved <= server.budget.total


def test_server_same_tick_contention_defers_not_sheds():
    """Two same-signature queries whose tickets cannot co-reside: the
    second DEFERS (ages in ticks_deferred, keeps no queue slot) and
    completes once the first releases its reservation."""
    tables = make_join_tables(400, 1500, seed=21)
    server0 = Q.QueryServer(measure_profile=False)
    probe = Q.QueryRequest(qid=99, plan=JOIN_PLAN, tables=tables)
    server0.submit(probe)
    server0.run()
    peak = server0.cache[probe.signature].peak_bytes
    assert peak > 0

    before = metrics.counter("qserve.mem_deferrals").value
    server = Q.QueryServer(measure_profile=False, slots_per_tick=2,
                           mem_budget_bytes=int(peak * 1.5))
    reqs = [Q.QueryRequest(qid=i, plan=JOIN_PLAN, tables=tables)
            for i in range(2)]
    drive(server, reqs, per_tick=2)
    for r in reqs:
        assert r.done and not r.error, (r.qid, r.detail)
        assert canon(*r.result) == one_shot(JOIN_PLAN, tables)
    assert metrics.counter("qserve.mem_deferrals").value > before
    assert reqs[1].ticks_deferred > 0
    assert reqs[0].ticks_deferred == 0
    assert server.budget.reserved == 0
    assert server.budget.peak_reserved <= server.budget.total


def test_server_deferred_request_does_not_starve_queue():
    """Regression: a memory-deferred request must NOT occupy a max_queue
    slot. With the old accounting a stuck query wedged a tiny queue and
    every later submission was shed."""
    tables = make_join_tables(350, 1300, seed=31)
    before_shed = metrics.counter("qserve.shed").value
    server = Q.QueryServer(measure_profile=False, max_queue=2,
                           slots_per_tick=2)
    # loses the (injected) allocation race on EVERY admission attempt:
    # permanently deferred until its deadline evicts it
    stuck = Q.QueryRequest(qid=0, plan=JOIN_PLAN, tables=tables,
                           fault_spec="oom:qserve.admit", deadline_ticks=8)
    server.submit(stuck)
    server.step()
    assert stuck in server.deferred and not server.queue
    later = [Q.QueryRequest(qid=1 + i, plan=JOIN_PLAN, tables=tables)
             for i in range(4)]
    for pair in (later[:2], later[2:]):
        for r in pair:
            server.submit(r)  # queue holds 2: at cap, NOT over it
        while server.queue:
            server.step()
    server.run()
    assert metrics.counter("qserve.shed").value == before_shed
    for r in later:
        assert r.done and not r.error, (r.qid, r.detail)
    assert stuck.error == "deadline"
    assert stuck.ticks_deferred > 0
    assert server.budget.reserved == 0


def test_chaos_smoke_single_family():
    """Tiny end-to-end chaos pass (full soak runs in scripts/ci.sh)."""
    from repro.serve import chaos

    rep = chaos.run_chaos(queries_per_family=24, smoke=True,
                          families=("estimates",))
    assert rep["ok"], rep["failures"]
    assert rep["baseline"]["p99_s"] > 0
    assert rep["baseline"]["throughput_qps"] > 0
    fam = rep["families"]["estimates"]
    assert fam["wrong_results"] == 0 and fam["contaminated"] == 0
    assert fam["counters"]["qserve.saturations"] > 0
