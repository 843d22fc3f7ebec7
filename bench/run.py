"""Run one benchmark cell once, in one process, on one chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of `workloads` in BENCHMARK.json, which names a
configuration (`bench/configs/<config>.json`: the tables and their data)
and a traffic mix (`bench/traffic/<traffic>.json`: the query and how it is
sent). Each metric of BENCHMARK.json is read by `bench/metrics/<name>.py`.
A new cell, mix or metric is a new file and a new entry; this file stays.

In order, one run:

1. checks the device: a TPU, as many chips as the cell asks for, and no
   override that forces a kernel arm other than the chip's;
2. sets up: turns on the compile cache in `<checkout>/.jax_cache` (and
   no other, whatever `JAX_COMPILATION_CACHE_DIR` said), makes
   the configuration's tables on the device from `--seed`, and submits the
   query once, which plans and compiles its one signature;
3. measures: one client submits the same query to `QueryServer` again and
   again (closed loop), syncing each answer with `block_until_ready`, and
   starts no query once `--seconds` of query time have passed. Between
   queries, off the clock, it copies the answer to the host and frees it
   on the device. With `--trace 1` the window's first query runs under
   the profiler;
4. reads the device's peak memory, frees the program's state, and checks
   every answer of the window against the NumPy reference
   (`bench/reference.py`);
5. prints the numbers compared, each beside its limit, as its last lines
   on standard error, and one JSON object as its last line on standard
   output.

It exits non-zero and prints no result off a TPU, with too few chips, or
without the program under test beside it. A run whose answers are wrong
still prints its result, with "correct": false, and exits 0.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_DIR = ROOT / ".bench_trace"
CACHE_DIR = ROOT / ".jax_cache"
LIMITS = {"rows_off": 0, "count_gap": 0}  # exact answers: no row may differ


class Unfit(Exception):
    """This machine or checkout cannot run the cell: no result is printed."""


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def load_cell(name: str, benchmark: dict | None = None) -> dict:
    """Everything one cell needs, found by the names in BENCHMARK.json."""
    if benchmark is None:
        benchmark = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in benchmark["workloads"]}
    if name not in cells:
        raise Unfit(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    configs = {c["name"]: c for c in benchmark["configs"]}
    config = json.loads((ROOT / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    if traffic["loop"] != "closed" or traffic["clients"] != 1:
        raise ValueError("the harness drives one client in a closed loop")

    def applies(metric):
        return name in metric.get("workloads", [name])

    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": [m for m in benchmark["end_to_end"] if applies(m)],
            "per_layer": [m for m in benchmark["per_layer"] if applies(m)]}


def reader(name: str):
    """The `read(record)` function of `bench/metrics/<name>.py`."""
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise Unfit(f"no peaks for device kind {device_kind!r} in bench/peaks.json")
    return table[device_kind]


def check_device(chips: int):
    """The chips the cell runs on, or Unfit."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise Unfit(f"needs a TPU, JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise Unfit(f"needs {chips} chips, JAX found {len(devices)}")
    from repro.kernels import common as kcommon
    from repro.kernels import ops as kops

    if kcommon.default_interpret():
        raise Unfit("REPRO_PALLAS_INTERPRET forces interpret mode")
    if kops.partition_plan_impl() != "pallas":
        raise Unfit("REPRO_PARTITION_PLAN_IMPL forces the XLA partition arm")
    return devices[:chips]


def peak_bytes_in_use(devices) -> int:
    """The device runtime's peak of bytes in use, on the fullest chip."""
    return max(int(d.memory_stats()["peak_bytes_in_use"]) for d in devices)


def host_answer(result) -> tuple[dict, int]:
    """A served (Table, count) copied to the host."""
    import numpy as np

    table, count = result
    return {c: np.asarray(table[c]) for c in table.column_names}, int(count)


def serve_one(server, plan, tables, qid: int):
    """Submit one query, step the server until it is answered, and sync
    the answer. Returns (request, seconds from submit to synced answer)."""
    import jax

    from repro.serve.query import QueryRequest

    t0 = time.perf_counter()
    req = QueryRequest(qid=qid, plan=plan, tables=tables)
    with jax.profiler.TraceAnnotation("bench.submit"):
        server.submit(req)
    while not req.done:
        with jax.profiler.TraceAnnotation("bench.step"):
            server.step()
    with jax.profiler.TraceAnnotation("bench.sync"):
        jax.block_until_ready(req.result)
    return req, time.perf_counter() - t0


def traced_one(server, plan, tables, qid: int):
    """`serve_one` inside a `bench.window` span, under the profiler."""
    import jax

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    jax.profiler.start_trace(str(TRACE_DIR))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            return serve_one(server, plan, tables, qid)
    finally:
        jax.profiler.stop_trace()


def measure(server, plan, tables, seconds: float, trace: bool) -> dict:
    """The closed-loop window: query after query until `seconds` of query
    time have passed. Returns the answers (on the host) and the timings."""
    latencies, exec_walls, answers, failed = [], [], [], 0
    pauses = []  # the interpreter's garbage-collection pauses, for the log
    t_gc = [0.0]

    def on_gc(phase, info):
        if phase == "start":
            t_gc[0] = time.perf_counter()
        else:
            pauses.append((info["generation"], time.perf_counter() - t_gc[0]))

    gc.callbacks.append(on_gc)
    while sum(latencies) < seconds:
        one = traced_one if trace and not latencies else serve_one
        req, wall = one(server, plan, tables, len(latencies) + 1)
        latencies.append(wall)
        exec_walls.append(req.exec_wall_s)
        log(f"query {req.qid}: {wall} s, exec_wall_s {req.exec_wall_s}, "
            f"gc pauses {pauses}")
        pauses.clear()
        if req.result is None:
            failed += 1
            log(f"query {req.qid} failed: {req.error} {req.detail}")
            continue
        answers.append(host_answer(req.result))  # off the clock
        req.result = None
    gc.callbacks.remove(on_gc)
    return {"latencies_s": latencies, "exec_walls_s": exec_walls,
            "answers": answers, "failed": failed}


def check(steps: list, host_tables: dict, answers: list) -> dict:
    """The numbers compared, summed over the answers, each with its limit."""
    import reference

    expected = reference.Expected(reference.evaluate(steps, host_tables))
    totals = dict.fromkeys(LIMITS, 0)
    for got, count in answers:
        for k, v in expected.compare(got, count).items():
            totals[k] += v
    return {"numbers": {k: {"value": v, "limit": LIMITS[k]}
                        for k, v in totals.items()},
            "out_rows": expected.rows}


def trace_record(n_traced: int) -> tuple[dict, dict]:
    """({busy_s, window_s, queries}, breakdown) from the traced window."""
    import tracing

    ops, spans = tracing.load(str(TRACE_DIR))
    lo, hi = tracing.window(spans, "bench.window")
    rec = {"busy_s": tracing.busy(ops, lo, hi), "window_s": hi - lo,
           "queries": n_traced}
    breakdown = {"device_ops": tracing.top_ops(ops, lo, hi),
                 "idle_gaps": tracing.idle_gaps(ops, spans, lo, hi)}
    return rec, breakdown


def run(args, cell: dict) -> dict:
    """One run of the cell: the result object the last line prints."""
    import jax
    import numpy as np

    from repro import compile_cache

    compile_cache.enable()  # before anything compiles
    log(f"compile cache: {jax.config.jax_compilation_cache_dir}")
    devices = check_device(cell["cell"]["chips"])
    dev = devices[0]
    peak = peaks(dev.device_kind)

    from repro.core.table import Table
    from repro.serve.query import QueryServer

    import datagen
    import plans

    config, steps = cell["config"], cell["traffic"]["plan"]
    cols = jax.block_until_ready(datagen.generate(config, args.seed))
    tables = {n: Table(c) for n, c in cols.items()}
    log(f"tables made at {time.perf_counter() - T_START:.3f} s; "
        f"peak_bytes_in_use {peak_bytes_in_use(devices)}")
    plan = plans.build(steps)
    server = QueryServer()
    first, _ = serve_one(server, plan, tables, 0)
    if first.result is None:
        raise RuntimeError(f"the first query failed: {first.error} {first.detail}")
    log(f"first query: plan_wall_s {first.plan_wall_s} exec_wall_s "
        f"{first.exec_wall_s} path {first.path}; "
        f"peak_bytes_in_use {peak_bytes_in_use(devices)}")
    plan_wall_s = first.plan_wall_s
    # the server keeps every request it completed: release the answer, as
    # the window does, or it stays on the device through the window
    first.result = first = None
    # collect the planner's garbage now, not in the window's first query
    gc.collect()
    setup_s = time.perf_counter() - T_START
    log(f"set-up done at {setup_s} s")

    win = measure(server, plan, tables, args.seconds, bool(args.trace))
    peak_bytes = peak_bytes_in_use(devices)

    # the program's state is freed before the reference runs
    host_tables = {n: {c: np.asarray(v) for c, v in t.items()}
                   for n, t in cols.items()}
    del server, tables, cols
    gc.collect()
    log(f"window: {len(win['latencies_s'])} queries, latencies "
        f"{win['latencies_s']}; peak_bytes_in_use {peak_bytes}")
    t_check = time.perf_counter()
    checked = check(steps, host_tables, win["answers"])
    log(f"reference and check took {time.perf_counter() - t_check:.3f} s")

    rows = {n: len(next(iter(t.values()))) for n, t in host_tables.items()}
    schemas = {n: list(t) for n, t in host_tables.items()}
    record = {
        "setup_s": setup_s, "plan_wall_s": plan_wall_s,
        "latencies_s": win["latencies_s"], "exec_walls_s": win["exec_walls_s"],
        "peak_bytes": peak_bytes, "peaks": peak,
        "least_bytes": plans.least_bytes(steps, rows, schemas, checked["out_rows"]),
        "trace": None,
    }
    breakdown = None
    if args.trace:
        t_trace = time.perf_counter()
        record["trace"], breakdown = trace_record(1)
        log(f"trace read in {time.perf_counter() - t_trace:.3f} s")
    names = cell["per_layer"] if args.trace else cell["end_to_end"]
    metrics = {}
    for m in names:
        value = reader(m["name"])(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    numbers = checked["numbers"]
    attempted = len(win["latencies_s"])
    correct = (win["failed"] == 0 and len(win["answers"]) > 0
               and all(n["value"] <= n["limit"] for n in numbers.values()))
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    if record["trace"]:
        device["busy_s"] = record["trace"]["busy_s"]
        device["window_s"] = record["trace"]["window_s"]
    out = {"correct": correct, "attempted": attempted, "failed": win["failed"],
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    log(f"answers checked: {len(win['answers'])}")
    out["check"] = numbers
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # the compile cache lives in the checkout, whatever the environment
    # says, and JAX reads this variable when it is first imported
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path.insert(0, str(ROOT / "src"))
    try:
        cell = load_cell(args.workload)
        out = run(args, cell)
    except (Unfit, ImportError, FileNotFoundError) as e:
        print(f"bench: cannot run {args.workload}: {type(e).__name__}: {e}",
              file=sys.stderr)
        return 2
    for name, n in out["check"].items():
        print(f"check {name} = {n['value']} (limit {n['limit']})",
              file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
