"""The program's own spans and scopes as the per-layer metrics read them
(`bench/spans.py`): by hand on built lists, on a small trace recorded on a
TPU v5e (`data/tiny_v5e_trace`), and on a traced run here."""
import pathlib

import pytest

import run
import spans
import tracing

# a while loop (w) whose body runs two ops (a, b), then a lone op (c)
OPS = [("w", 1.0, 5.0), ("a", 1.0, 2.0), ("b", 3.0, 4.0), ("c", 6.0, 8.0)]


def seconds(pairs):
    return {op[0]: pytest.approx(s) for op, s in pairs}


def test_innermost_op_takes_each_busy_instant():
    # the body's ops take their own time, the loop the rest of its span;
    # on a tie of starts the shorter op is the inner one
    assert seconds(spans.innermost(OPS, 0.0, 10.0)) == {
        "a": 1.0, "b": 1.0, "w": 2.0, "c": 2.0}
    # clipped to the window, and summing to the busy time
    got = spans.innermost(OPS, 1.5, 7.0)
    assert seconds(got) == {"a": 0.5, "b": 1.0, "w": 2.0, "c": 1.0}
    assert sum(s for _, s in got) == pytest.approx(tracing.busy(OPS, 1.5, 7.0))
    assert spans.innermost([], 0.0, 1.0) == []


def trace_of(ops, scopes, modules):
    return {"ops": ops, "spans": [], "programs": {"jit_served_plan": scopes},
            "modules": modules}


def test_phases_sum_to_busy_and_only_the_traced_module_is_scoped():
    scopes = {"w": "join.phj/probe", "a": "join.phj/partition",
              "b": "groupby.sort", "c": "join.phj/materialize"}
    # c runs outside any execution of the traced module: eager work
    trace = trace_of(OPS, scopes, [("jit_served_plan", 0.5, 5.5),
                                   ("jit_pad", 5.9, 8.5)])
    assert spans.scope_seconds(trace, 0.0, 10.0) == {
        "join.phj/partition": pytest.approx(1.0), "groupby.sort": pytest.approx(1.0),
        "join.phj/probe": pytest.approx(2.0), "": pytest.approx(2.0)}
    phases = spans.phase_seconds(trace, 0.0, 10.0)
    assert phases == {"partition": pytest.approx(1.0), "probe": pytest.approx(2.0),
                      "materialize": 0.0, "aggregate": 0.0,
                      "unscoped": pytest.approx(3.0)}
    assert sum(phases.values()) == pytest.approx(tracing.busy(OPS, 0.0, 10.0))


def test_idle_time_goes_to_the_innermost_span():
    # idle: [0, 1], [5, 6] and [8, 10]; the server pads in [0.2, 0.8]
    spans_ = [("bench.window", 0.0, 10.0), ("bench.step", 0.0, 9.0),
              ("qserve.pad", 0.2, 0.8), ("qserve.count_sync", 1.0, 9.0)]
    trace = trace_of(OPS, {}, [])
    trace["spans"] = spans_
    assert spans.idle_by_span(trace, 0.0, 10.0) == {
        "bench.step": pytest.approx(0.4), "qserve.pad": pytest.approx(0.6),
        "qserve.count_sync": pytest.approx(2.0), "bench.window": pytest.approx(1.0)}
    idle = 10.0 - tracing.busy(OPS, 0.0, 10.0)
    assert sum(spans.idle_by_span(trace, 0.0, 10.0).values()) == pytest.approx(idle)
    trace["spans"] = []
    assert spans.idle_by_span(trace, 0.0, 10.0) == {"none": pytest.approx(idle)}


def test_scope_metadata_decodes_and_names_its_phase():
    text = "join.phj/probe while.4 fusion.7|groupby.sort/aggregate fusion.9"
    assert spans.decode_scopes(text) == {
        "while.4": "join.phj/probe", "fusion.7": "join.phj/probe",
        "fusion.9": "groupby.sort/aggregate"}
    assert spans.decode_scopes("") == {}
    assert spans.instruction("%fusion.33 = s32[8]{0} fusion(s32[8]{0} %p)") == "fusion.33"
    assert spans.instruction("copy-start") == "copy-start"
    assert [spans.phase_of(p) for p in (
        "join.phj/partition", "groupby.sort", "", None, "partition/aggregate")] == [
        "partition", "unscoped", "unscoped", "unscoped", "aggregate"]


TINY = pathlib.Path(__file__).parent / "data" / "tiny_v5e_trace"


def test_recorded_v5e_trace():
    trace = spans.load(str(TINY))
    assert trace["programs"] == {}  # recorded before the program sent its map
    lo, hi = tracing.window(trace["spans"], "bench.window")
    busy = tracing.busy(trace["ops"], lo, hi)
    got = spans.innermost(trace["ops"], lo, hi)
    assert busy > 0 and sum(s for _, s in got) == pytest.approx(busy)
    # the recorded module's executions, with a map for two of its ops
    assert {m for m, _, _ in trace["modules"]} == {"jit__lambda"}
    trace["programs"] = {"jit__lambda": {"reduce-window": "groupby.sort/partition",
                                         "rev.1": "join.phj/probe"}}
    phases = spans.phase_seconds(trace, lo, hi)
    assert phases["partition"] > 0 and phases["probe"] > 0
    assert phases["materialize"] == phases["aggregate"] == 0.0
    assert sum(phases.values()) == pytest.approx(busy)
    idle = spans.idle_by_span(trace, lo, hi)
    assert set(idle) <= {"bench.window", "bench.step", "bench.sync", "none"}
    assert sum(idle.values()) == pytest.approx(hi - lo - busy)


PROGRAM_METRICS = {"plan_stats_s", "plan_audit_s", "compile_s", "host_dispatch_ms"}


def test_a_traced_run_reports_the_program_spans(cpu_run):
    from conftest import tiny

    from repro.obs import metrics

    metrics.reset()  # a run has a process, and so a registry, of its own
    out = cpu_run(tiny("tpch-q18-sf10.join-groupby"), trace=1)
    assert out["correct"]
    # the CPU has no TPU plane: every device metric stays unread
    assert set(out["metrics"]) == {"server_admit_ms", "plan_s"} | PROGRAM_METRICS
    for name in PROGRAM_METRICS:
        assert out["metrics"][name]["value"] > 0, name
    # the traced window's exec.run span carries the served executable's map
    trace = spans.load(str(run.TRACE_DIR))
    paths = set(trace["programs"]["jit_served_plan"].values())
    assert {spans.phase_of(p) for p in paths} >= {"partition", "probe", "aggregate"}
    assert spans.traced_phase_s({"trace": out["device"]}, "probe") is None
    # no device op on the CPU: the whole window is idle, in the program's spans
    lo, hi = tracing.window(trace["spans"], "bench.window")
    assert {"qserve.pad", "qserve.count_sync"} <= set(spans.idle_by_span(trace, lo, hi))
