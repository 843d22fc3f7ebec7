"""The trace reduction, by hand on a built list and on a small trace
recorded on a TPU v5e (`data/tiny_v5e_trace`)."""
import pathlib

import pytest

import tracing

OPS = [("a", 1.0, 2.0), ("b", 1.5, 3.0), ("a", 5.0, 6.0), ("c", 9.0, 12.0)]
SPANS = [("bench.window", 0.0, 10.0), ("bench.step", 0.5, 4.0),
         ("bench.sync", 4.0, 10.0)]


def test_busy_union_clips_to_the_window():
    # [1, 3] and [5, 6] inside; [9, 12] clipped to [9, 10]
    assert tracing.busy(OPS, 0.0, 10.0) == pytest.approx(4.0)
    assert tracing.merged([(s, e) for _, s, e in OPS], 0.0, 10.0) == [
        (1.0, 3.0), (5.0, 6.0), (9.0, 10.0)]


def test_top_ops_sum_their_events():
    assert tracing.top_ops(OPS, 0.0, 10.0) == [
        ["a", pytest.approx(2.0)], ["b", pytest.approx(1.5)], ["c", pytest.approx(1.0)]]
    assert tracing.top_ops(OPS, 0.0, 10.0, n=1) == [["a", pytest.approx(2.0)]]


def test_idle_gaps_are_named_by_the_innermost_span():
    # gaps: [0, 1] (step open at 0.5), [3, 5] (mid 4.0: sync), [6, 9] (sync)
    assert tracing.idle_gaps(OPS, SPANS, 0.0, 10.0) == [
        ["bench.sync", pytest.approx(3.0)], ["bench.sync", pytest.approx(2.0)],
        ["bench.step", pytest.approx(1.0)]]
    assert tracing.idle_gaps([], [], 0.0, 2.0) == [["none", pytest.approx(2.0)]]


def test_short_names_keep_instruction_op_and_shape():
    assert tracing.short_name(
        "%fusion.31 = s32[67108864]{0:T(1024)} fusion(s32[67108864]{0:T(1024)} "
        "%tb.1), kind=kCustom, calls=%fused_computation.9.clone") == (
        "%fusion.31 fusion s32[67108864]")
    assert tracing.short_name(
        "%while.4 = (s32[]{:T(128)}, pred[8,8]{1,0}) while((s32[]{:T(128)}, "
        "pred[8,8]{1,0}) %tuple.47), condition=%c") == "%while.4 while (tuple)"
    assert tracing.short_name("copy-start") == "copy-start"


def test_window_needs_exactly_one_span():
    assert tracing.window(SPANS, "bench.window") == (0.0, 10.0)
    with pytest.raises(ValueError):
        tracing.window(SPANS + SPANS, "bench.window")


TINY = pathlib.Path(__file__).parent / "data" / "tiny_v5e_trace"


def test_recorded_v5e_trace():
    ops, spans = tracing.load(str(TINY))
    names = {s[0] for s in spans}
    assert {"bench.window", "bench.step", "bench.sync"} <= names
    lo, hi = tracing.window(spans, "bench.window")
    assert ops and hi > lo
    b = tracing.busy(ops, lo, hi)
    assert 0 < b < hi - lo
    assert sum(v for _, v in tracing.top_ops(ops, lo, hi, n=len(ops))) >= b
    gaps = tracing.idle_gaps(ops, spans, lo, hi, n=10_000)
    assert sum(v for _, v in gaps) == pytest.approx(hi - lo - b)
    assert {tracing.short_name(n) for n, _, _ in ops} >= {
        "%multiply_add_fusion fusion s32[1048576]", "%iota iota s32[1048576]"}


def test_a_traced_run_reports_the_per_layer_metrics(cpu_run):
    from conftest import tiny
    from test_spans import PROGRAM_METRICS

    from repro.obs import metrics

    metrics.reset()  # a run has a process, and so a registry, of its own
    out = cpu_run(tiny("tpch-q7-sf10.join"), trace=1)
    assert out["correct"]
    # the CPU has no TPU plane: no op is found, so no device metric is read;
    # the program's spans are
    assert set(out["metrics"]) == {"server_admit_ms", "plan_s"} | PROGRAM_METRICS
    assert out["device"]["window_s"] > 0 and out["device"]["busy_s"] == 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
