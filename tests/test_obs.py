"""Observability-layer tests (DESIGN.md §12): span tracer correctness and
zero-overhead contract, residual EWMAs + regret flags, the persistent
calibration store (cross-process round-trip, env-override validation,
per-(backend, n) profile cache), metrics counters, and the CLI."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Table
from repro.core.planner import PrimitiveProfile
from repro.engine import Catalog, Optimizer, executor, scan
from repro.engine import physical as P
from repro.obs import (CalibrationStore, NodeResidual, ResidualStore, Span,
                       backend_fingerprint, calibration_path, metrics,
                       regret_check, residuals_of)

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "src")


@pytest.fixture
def calstore_path(tmp_path, monkeypatch):
    """Point the calibration store at a scratch file so tests never touch
    (or depend on) a real CALIBRATION.json in the cwd."""
    path = tmp_path / "CALIBRATION.json"
    monkeypatch.setenv("REPRO_CALIBRATION_PATH", str(path))
    return path


def _star_plan(n_r=64, n_s=512, seed=0):
    rng = np.random.default_rng(seed)
    R = Table({"k": jnp.asarray(rng.permutation(n_r).astype(np.int32)),
               "rv": jnp.asarray(rng.integers(0, 50, n_r).astype(np.int32))})
    S = Table({"k": jnp.asarray(rng.integers(0, n_r, n_s).astype(np.int32)),
               "g": jnp.asarray(rng.integers(0, 8, n_s).astype(np.int32)),
               "sv": jnp.asarray(rng.integers(0, 50, n_s).astype(np.int32))})
    cat = Catalog({"R": R, "S": S})
    q = scan("S").join(scan("R"), key="k").group_by("g", rv="sum", sv="sum")
    return Optimizer(cat, measure_profile=False).optimize(q)


# ---------------------------------------------------------------------------
# Span tracer
# ---------------------------------------------------------------------------
def test_traced_run_matches_untraced():
    plan = _star_plan()
    t_ref, c_ref = plan.run()
    t_tr, c_tr, trace = plan.run(trace=True)
    assert int(c_tr) == int(c_ref)
    n = int(c_ref)
    for col in t_ref.column_names:
        np.testing.assert_array_equal(np.asarray(t_ref[col])[:n],
                                      np.asarray(t_tr[col])[:n])
    # every physical node produced a span; the root is the group side
    assert trace.root.op in ("groupby", "groupjoin")
    assert all(s.wall_s > 0 for s in trace.spans())
    assert trace.root.rows_out == n


def test_trace_overhead_bound_accounts_for_e2e():
    """Acceptance check: per-node measured times sum to within the trace's
    own overhead bound of the untraced end-to-end time."""
    plan = _star_plan()
    _, _, trace = plan.run(trace=True, trace_iters=3, trace_warmup=1)
    assert trace.e2e_wall_s > 0
    assert abs(trace.sum_wall_s - trace.e2e_wall_s) <= trace.overhead_bound_s


def test_untraced_run_is_zero_overhead():
    """trace=False takes the untraced code path: no Span allocated, and
    the whole-plan jaxpr is identical after a traced run happened."""
    plan = _star_plan()
    tables = dict(plan.catalog.tables)
    jaxpr_before = str(jax.make_jaxpr(
        lambda tb: executor.execute(plan.root, tb))(tables))
    before = Span.allocated
    plan.run()
    plan.run()  # cached-executable path too
    assert Span.allocated == before  # no span objects on the untraced path
    _, _, trace = plan.run(trace=True)
    assert Span.allocated > before  # the traced path does allocate
    assert len(trace.spans()) == Span.allocated - before
    jaxpr_after = str(jax.make_jaxpr(
        lambda tb: executor.execute(plan.root, tb))(tables))
    assert jaxpr_after == jaxpr_before


def test_trace_exports(tmp_path):
    plan = _star_plan()
    _, _, trace = plan.run(trace=True)
    d = trace.as_dict()
    assert d["backend"] == backend_fingerprint()
    for node in d["nodes"]:
        for key in ("op", "path", "strategy", "predicted_s", "measured_s",
                    "residual", "rows_in", "rows_out", "bytes_in",
                    "bytes_out"):
            assert key in node
    tj = tmp_path / "TRACE.json"
    trace.to_json(str(tj))
    assert json.loads(tj.read_text())["nodes"]
    # the rendered table carries the predicted-vs-measured comparison
    tbl = trace.table()
    assert "predicted" in tbl and "measured" in tbl and "residual" in tbl


def test_explain_with_actuals_annotates_every_line():
    plan = _star_plan()
    _, _, trace = plan.run(trace=True)
    out = plan.explain(actuals=trace)
    assert "predicted[" in out and "measured[" in out and "residual[" in out
    # unpriced nodes (scans) render a residual placeholder, not a crash
    assert "residual[-]" in out


# ---------------------------------------------------------------------------
# Residuals + regret
# ---------------------------------------------------------------------------
def test_residual_store_ewma_update():
    rs = ResidualStore()
    r = NodeResidual(op="groupby", strategy="partition",
                     predicted_s=1.0, measured_s=2.0)
    rs.update([r])
    assert rs.correction("groupby", "partition") == pytest.approx(2.0)
    rs.update([NodeResidual(op="groupby", strategy="partition",
                            predicted_s=1.0, measured_s=4.0)])
    assert rs.correction("groupby", "partition") == pytest.approx(
        0.7 * 2.0 + 0.3 * 4.0)
    ent = rs.data["groupby/partition"]
    assert ent["count"] == 2 and ent["last"] == pytest.approx(4.0)
    assert rs.correction("groupby", "sort") == 1.0  # unobserved -> neutral
    # round-trips through its dict form
    rs2 = ResidualStore.from_dict(json.loads(json.dumps(rs.as_dict())))
    assert rs2.correction("groupby", "partition") == pytest.approx(
        rs.correction("groupby", "partition"))


def test_residuals_of_skips_unpriced_nodes():
    plan = _star_plan()
    _, _, trace = plan.run(trace=True)
    res = residuals_of(trace)
    assert res and all(r.predicted_s > 0 for r in res)
    assert all(r.ratio > 0 for r in res)
    assert not any(r.op == "scan" for r in res)


def test_regret_check():
    rs = ResidualStore({"groupby/partition": {"ewma": 10.0, "count": 3,
                                              "last": 10.0},
                        "groupby/sort": {"ewma": 1.0, "count": 3,
                                         "last": 1.0}})
    choices = {"partition": 1.0, "sort": 1.1}
    msg = regret_check(rs, "groupby", choices, "partition")
    assert msg.startswith("REGRET:") and "partition" in msg and "sort" in msg
    # the chosen strategy was never observed -> no claim to make
    assert regret_check(ResidualStore(), "groupby", choices, "partition") == ""
    # choice survives correction -> no flag
    ok = ResidualStore({"groupby/partition": {"ewma": 1.0, "count": 1,
                                              "last": 1.0}})
    assert regret_check(ok, "groupby", choices, "partition") == ""


def test_optimizer_attaches_regret_flag():
    """A plan whose predicted winner lost by >2x in the residual store
    carries the REGRET annotation in explain()."""
    n = 2048
    rng = np.random.default_rng(3)
    keys = (rng.permutation(n) * 97).astype(np.int32)
    T = Table({"k": jnp.asarray(keys),
               "v": jnp.asarray(rng.normal(size=n).astype(np.float32))})
    cat = Catalog({"T": T})
    q = scan("T").group_by("k", v="sum")
    neutral = Optimizer(cat, measure_profile=False,
                        residuals=ResidualStore()).optimize(q)
    assert "GroupBy[partition]" in neutral.explain()
    assert "REGRET" not in neutral.explain()
    burned = ResidualStore({"groupby/partition": {"ewma": 50.0, "count": 2,
                                                  "last": 50.0},
                            "groupby/sort": {"ewma": 1.0, "count": 2,
                                             "last": 1.0}})
    plan = Optimizer(cat, measure_profile=False,
                     residuals=burned).optimize(q)
    assert "GroupBy[partition]" in plan.explain()  # advisory: choice stands
    assert "REGRET" in plan.explain()


# ---------------------------------------------------------------------------
# Calibration store
# ---------------------------------------------------------------------------
def test_calibration_path_validation(tmp_path, monkeypatch):
    monkeypatch.delenv("REPRO_CALIBRATION_PATH", raising=False)
    assert calibration_path() == "CALIBRATION.json"
    ok = tmp_path / "cal.json"
    monkeypatch.setenv("REPRO_CALIBRATION_PATH", str(ok))
    assert calibration_path() == str(ok)
    for bad in ("", "   "):
        monkeypatch.setenv("REPRO_CALIBRATION_PATH", bad)
        with pytest.raises(ValueError, match="REPRO_CALIBRATION_PATH"):
            calibration_path()
    monkeypatch.setenv("REPRO_CALIBRATION_PATH", str(tmp_path))
    with pytest.raises(ValueError, match="directory"):
        calibration_path()
    monkeypatch.setenv("REPRO_CALIBRATION_PATH",
                       str(tmp_path / "no_such_dir" / "cal.json"))
    with pytest.raises(ValueError, match="does not exist"):
        calibration_path()


def test_calibration_store_profile_roundtrip(calstore_path):
    store = CalibrationStore()
    prof = PrimitiveProfile(seq_bw=1e9, sort_pass_bw=2e8,
                            partition_pass_bw=3e8,
                            unclustered_penalty=4.0, clustered_penalty=1.5)
    store.put_profile("fp-a", 4096, prof)
    store.save()
    again = CalibrationStore()
    got = again.get_profile("fp-a", 4096)
    assert got == prof
    assert again.get_profile("fp-a", 8192) is None  # keyed by n
    assert again.get_profile("fp-b", 4096) is None  # keyed by backend
    # schema drift (missing constants) falls back to None, not half a profile
    again.data["fp-a"]["profiles"]["4096"].pop("seq_bw")
    assert again.get_profile("fp-a", 4096) is None
    # corrupt file tolerated: store starts empty
    calstore_path.write_text("{not json")
    assert CalibrationStore().data == {}


def test_calibrated_profile_cache_keyed_by_backend_and_n(calstore_path,
                                                         monkeypatch):
    """Satellite fix: the in-process profile cache must key by (backend, n),
    not be a single global slot — different calibration sizes coexist and
    a repeated call never re-measures."""
    calls = []

    def fake_measure(cls, n=1 << 16, **kw):
        calls.append(n)
        return PrimitiveProfile(seq_bw=float(n), sort_pass_bw=1.0,
                                partition_pass_bw=1.0,
                                unclustered_penalty=1.0,
                                clustered_penalty=1.0)

    monkeypatch.setattr(PrimitiveProfile, "measure",
                        classmethod(fake_measure))
    monkeypatch.setattr(P, "_PROFILE_CACHE", {})
    p1 = P.calibrated_profile(n=1024)
    p2 = P.calibrated_profile(n=2048)
    assert (p1.seq_bw, p2.seq_bw) == (1024.0, 2048.0)
    assert calls == [1024, 2048]
    assert P.calibrated_profile(n=1024) is p1  # cached, not re-measured
    assert calls == [1024, 2048]
    fp = backend_fingerprint()
    assert {(fp, 1024), (fp, 2048)} <= set(P._PROFILE_CACHE)


def test_calibrated_profile_persists_across_processes(calstore_path):
    """Acceptance check: process one measures and persists; process two
    (measurement poisoned) loads the stored profile from CALIBRATION.json
    instead of re-running the microbenchmarks."""
    env = dict(os.environ, PYTHONPATH=SRC,
               REPRO_CALIBRATION_PATH=str(calstore_path))
    first = (
        "from repro.core.planner import PrimitiveProfile\n"
        "from repro.engine import calibrated_profile\n"
        "PrimitiveProfile.measure = classmethod(\n"
        "    lambda cls, n=0, **kw: PrimitiveProfile(seq_bw=123.0,\n"
        "        sort_pass_bw=1.0, partition_pass_bw=1.0,\n"
        "        unclustered_penalty=1.0, clustered_penalty=1.0))\n"
        "print(calibrated_profile(n=4096).seq_bw)\n")
    second = (
        "from repro.core.planner import PrimitiveProfile\n"
        "def boom(*a, **kw): raise AssertionError('re-measured')\n"
        "PrimitiveProfile.measure = classmethod(boom)\n"
        "from repro.engine import calibrated_profile\n"
        "print(calibrated_profile(n=4096).seq_bw)\n")
    for code in (first, second):
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, check=True)
        assert out.stdout.strip().endswith("123.0"), out.stdout
    saved = json.loads(calstore_path.read_text())
    fp = next(iter(saved))
    assert saved[fp]["profiles"]["4096"]["seq_bw"] == 123.0


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------
def test_metrics_registry_basics():
    reg = metrics.MetricsRegistry()
    reg.counter("a").inc()
    reg.counter("a").inc(2)
    reg.histogram("h").observe(1.0)
    reg.histogram("h").observe(3.0)
    snap = reg.snapshot()
    assert snap["a"] == 3
    assert snap["h"]["count"] == 2 and snap["h"]["max"] == 3.0
    with pytest.raises(TypeError):
        reg.histogram("a")  # kind mismatch on an existing name
    reg.reset()
    assert reg.snapshot() == {}


def test_engine_metrics_counters():
    plan = _star_plan(seed=1)
    metrics.reset()
    plan.run()
    plan.run()
    snap = metrics.snapshot()
    assert snap.get("engine.plans_compiled", 0) >= 1
    assert snap.get("engine.plan_cache_hits", 0) >= 1


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------
def test_obs_cli_smoke(tmp_path, monkeypatch, calstore_path):
    """`python -m repro.obs --smoke` end to end: traced workload, TRACE
    files written with full schemas, CALIBRATION.json gains residuals."""
    from repro.obs.__main__ import main

    # pre-seed the profile so the CLI loads it instead of measuring
    store = CalibrationStore()
    store.put_profile(backend_fingerprint(), 1 << 16, PrimitiveProfile())
    store.save()
    monkeypatch.setattr(P, "_PROFILE_CACHE", {})
    monkeypatch.chdir(tmp_path)
    rc = main(["--smoke", "--iters", "1", "--warmup", "1"])
    assert rc == 0
    tr = json.loads((tmp_path / "TRACE.json").read_text())
    assert set(tr["queries"]) == {"star", "highcard_groupby"}
    for q in tr["queries"].values():
        assert all("residual" in n and n["measured_s"] > 0
                   for n in q["nodes"])
    cal = json.loads(calstore_path.read_text())
    ent = cal[backend_fingerprint()]
    assert ent["profiles"] and ent["residuals"]
    assert any(k.startswith(("groupby/", "groupjoin/", "join/"))
               for k in ent["residuals"])


def test_metrics_percentiles_nearest_rank():
    vals = list(range(1, 101))  # 1..100
    p = metrics.percentiles(vals, (50, 95, 99))
    assert p == {"p50": 50.0, "p95": 95.0, "p99": 99.0}
    assert metrics.percentiles([], (50,)) == {"p50": 0.0}
    assert metrics.percentiles([7.0], (50, 99)) == {"p50": 7.0, "p99": 7.0}
    # fractional percentile labels format cleanly
    assert metrics.percentiles(vals, (99.9,)) == {"p99.9": 100.0}


def test_histogram_summary_and_bounded_samples():
    h = metrics.Histogram("t")
    assert h.summary() == {"count": 0, "mean": 0.0, "min": 0.0, "max": 0.0,
                           "p50": 0.0, "p95": 0.0, "p99": 0.0}
    for v in range(20_000):
        h.observe(float(v))
    # the sample buffer is decimated deterministically, never unbounded
    assert len(h.samples) < metrics.SAMPLE_CAP
    assert h.stride > 1
    s = h.summary()
    assert s["count"] == 20_000 and s["min"] == 0.0 and s["max"] == 19_999.0
    # stride-thinned percentiles stay representative of the full stream
    assert abs(s["p50"] - 10_000) < 1_000
    assert abs(s["p99"] - 19_800) < 1_000
    # as_value (the snapshot shape) is unchanged by the sample buffer
    assert set(h.as_value()) == {"count", "sum", "mean", "min", "max", "last"}


def test_span_times_into_its_histogram_and_the_profiler_trace(tmp_path):
    """A span observes its wall seconds (also when its body raises) and,
    under a profiler, lands as a host event carrying its metadata."""
    import glob

    from jax.profiler import ProfileData

    h = metrics.histogram("test.span")
    before = h.count
    with metrics.span("test.span"):
        pass
    with pytest.raises(ValueError):
        with metrics.span("test.span"):
            raise ValueError("body failed")
    assert h.count == before + 2 and h.total > 0
    jax.profiler.start_trace(str(tmp_path))
    try:
        with metrics.span("test.span", note="one piece"):
            jnp.arange(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    found = [dict(ev.stats) for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events
             if ev.name == "test.span"]
    assert found and found[0].get("note") == "one piece"
    assert h.count == before + 3
