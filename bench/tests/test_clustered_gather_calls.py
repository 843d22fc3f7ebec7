"""The `clustered_gather_calls` reader: kernel executions per traced query,
counted by instruction name inside the harness's window."""
import run

READ = run.reader("clustered_gather_calls")


def test_no_trace_reads_nothing():
    assert READ({"trace": None}) is None


def test_counts_only_the_kernel_inside_the_window(monkeypatch):
    import spans

    ops = [
        ("%clustered_gather.3 = s32[29280,1,1024]{2,1,0} custom-call(...)", 1.0, 1.1),
        ("%clustered_gather = s32[8,1,1024]{2,1,0} custom-call(...)", 2.0, 2.1),
        ("%clustered_gather.7 = s32[8,1,1024]{2,1,0} custom-call(...)", 11.0, 11.1),
        ("%clustered_gather_fusion.2 = s32[8]{0} fusion(...)", 3.0, 3.1),
        ("%fusion.58 = s32[29982720]{0} fusion(...)", 4.0, 4.8),
        ("%partition_ranks.1 = s32[64,128]{1,0} custom-call(...)", 5.0, 5.1),
    ]
    trace = {"ops": ops, "spans": [("bench.window", 0.5, 10.0)],
             "programs": {}, "modules": []}
    monkeypatch.setattr(spans, "load", lambda path: trace)
    assert READ({"trace": {"queries": 1}}) == 2
    assert READ({"trace": {"queries": 2}}) == 1.0
    # a program that gathers through XLA: device work, no kernel
    trace["ops"] = ops[4:]
    assert READ({"trace": {"queries": 1}}) == 0
    # no TPU plane (a CPU run): nothing to read
    trace["ops"] = []
    assert READ({"trace": {"queries": 1}}) is None
