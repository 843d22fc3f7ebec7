"""A traffic mix, a configuration or a metric is found by its name alone:
adding one adds files and BENCHMARK.json entries, and no harness code."""
import json
import uuid

import run


def test_files_dropped_in_are_found_by_name(cpu_run):
    tag = uuid.uuid4().hex[:8]
    traffic = run.BENCH / "traffic" / f"probe-{tag}.json"
    config = run.BENCH / "configs" / f"probe-{tag}.json"
    metric = run.BENCH / "metrics" / f"probe_{tag}.py"
    bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    base = json.loads((run.BENCH / "configs" / "tpch-q18-sf10.json").read_text())
    for t in base["tables"].values():
        t["rows"] //= 100_000
    try:
        traffic.write_text(json.dumps({"loop": "closed", "clients": 1, "plan": [
            ["scan", "S"], ["group_by", {"key": "k", "aggs": {"s1_lo": "sum"}}]]}))
        config.write_text(json.dumps(base))
        metric.write_text("def read(record):\n    return len(record['latencies_s'])\n")
        bench["configs"].append({"name": f"probe-{tag}", "file": f"bench/configs/probe-{tag}.json"})
        bench["workloads"].append({"name": f"probe-{tag}.cell", "config": f"probe-{tag}",
                                   "traffic": f"probe-{tag}", "chips": 1, "why": "probe"})
        bench["end_to_end"].append({"name": f"probe_{tag}", "unit": "1",
                                    "workloads": [f"probe-{tag}.cell"]})
        cell = run.load_cell(f"probe-{tag}.cell", bench)
        out = cpu_run(cell)
        assert out["correct"], out
        assert out["metrics"][f"probe_{tag}"]["value"] == out["attempted"]
        other = run.load_cell("tpch-q7-sf10.join", bench)
        assert f"probe_{tag}" not in [m["name"] for m in other["end_to_end"]]
    finally:
        for p in (traffic, config, metric):
            p.unlink(missing_ok=True)
