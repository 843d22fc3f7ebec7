"""Bring-up smoke: serve the TPC-H Q18 join and group-by on one TPU chip.

Drives the served relational path — `serve.query.QueryServer` planning with
`engine.physical.optimize` and running with `engine.executor.run` — on the
Table 6 extract J2 (TPC-H Q18 at SF10: `orders` 15,000,000 rows as R,
`lineitem` 60,000,000 rows as S), made from `--seed`. Each of three
datasets (seed, seed+1, seed+2) gets two requests:

  join     scan("S").join(scan("R"), key="k")                 PK-FK join
  groupby  scan("S").join(scan("R"), key="k")
               .group_by("k", s1="sum")                        Q18 shape

Every answer is checked against a plain NumPy reference (sort plus
searchsorted for the join, bincount over the dense keys for the group-by),
order-insensitively. The run fails unless the device is a TPU, no override
forces a non-chip kernel arm, every request took the fast path, no kernel
fallback or fast-path failure was counted, no plan is DEGRADED, and the
fast-path executable contains a Pallas kernel (`tpu_custom_call`).

It prints compile seconds and compile-cache hits, per-request wall times,
the chosen plans and the device's peak memory — a bring-up record, not a
benchmark — and as its last line one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}.

Usage: python chip_smoke.py [--seed N]
The compile cache goes to $JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

from repro.data.relgen import generate_tpc  # noqa: E402
from repro.engine.logical import scan  # noqa: E402
from repro.serve.query import QueryRequest, QueryServer  # noqa: E402

WORKLOAD = "J2"  # Table 6: TPC-H Q18, orders x lineitem
# x64 is off, so payload columns are 4-byte ints (the extract's default is
# 8 bytes); keys are 4 bytes as in the extract
KEY_BYTES, PAYLOAD_BYTES = 4, 4
CUTS = ["payload columns 4 B int32 instead of 8 B (x64 off)"]
SCALE = 1.0  # the whole extract: SF10
REQUESTS = 3  # datasets, each served to both queries; the first compiles


def join_query():
    """(a) The materialized PK-FK join: lineitem probes orders."""
    return scan("S").join(scan("R"), key="k")


def groupby_query():
    """(b) The Q18 shape: the join under a group-by on the order key, which
    the optimizer may fuse into a group-join."""
    return join_query().group_by("k", s1="sum")


QUERIES = {"join": join_query, "groupby": groupby_query}


# ---------------------------------------------------------------------------
# plain NumPy references
# ---------------------------------------------------------------------------
def _pack(hi: np.ndarray, lo: np.ndarray) -> np.ndarray:
    """Two int32 columns -> one int64 whose order is (hi, lo) order."""
    return (hi.astype(np.int64) << 32) | lo.astype(np.uint32).astype(np.int64)


def reference_join(R: dict, S: dict) -> dict:
    """The join's rows as sorted packed (k, column) pairs, one array per
    non-key output column. R's keys must be unique (a primary key), so every
    R column is a function of k and the pairs determine the row multiset."""
    order = np.argsort(R["k"], kind="stable")
    rk = R["k"][order]
    if np.any(rk[1:] == rk[:-1]):
        raise ValueError("reference join needs unique build keys")
    ks = np.sort(_pack(S["k"], S["s1"]))  # S rows sorted by (k, s1)
    k = (ks >> 32).astype(np.int32)
    pos = np.minimum(np.searchsorted(rk, k), len(rk) - 1)
    hit = rk[pos] == k
    ks, k, src = ks[hit], k[hit], order[pos[hit]]
    out = {"s1": ks}
    for c in R:
        if c != "k":
            out[c] = _pack(k, R[c][src])  # constant per k: already sorted
    return out


def reference_groupby(R: dict, S: dict) -> tuple[np.ndarray, np.ndarray]:
    """Per dense key k: (present, sum of s1 over S rows whose k is in R),
    the sum wrapped to int32 as the device's int32 accumulation does."""
    n = int(max(R["k"].max(), S["k"].max())) + 1
    in_r = np.bincount(R["k"], minlength=n) > 0
    m = in_r[S["k"]]
    present = np.bincount(S["k"][m], minlength=n) > 0
    sums = np.bincount(S["k"][m], weights=S["s1"][m].astype(np.float64),
                       minlength=n)
    return present, sums.astype(np.int64).astype(np.int32)


def check_join(got: dict, count: int, ref: dict) -> str:
    """'' when the first `count` rows of `got` equal the reference rows as
    a multiset, else what differs."""
    n = len(ref["s1"])
    if count != n:
        return f"join returned {count} rows, reference has {n}"
    k = got["k"][:count]
    for c, want in ref.items():
        have = np.sort(_pack(k, got[c][:count]))
        if not np.array_equal(have, want):
            bad = int(np.count_nonzero(have != want))
            return f"join column {c}: {bad} of {n} (k, {c}) pairs differ"
    return ""


def check_groupby(got: dict, count: int, ref) -> str:
    present, sums = ref
    k = got["k"][:count]
    if count != int(present.sum()):
        return f"group-by returned {count} groups, reference has {int(present.sum())}"
    if k.min(initial=0) < 0 or k.max(initial=0) >= len(present):
        return "group-by returned keys outside the reference's key range"
    if not np.array_equal(np.bincount(k, minlength=len(present)) > 0, present) \
            or len(np.unique(k)) != count:
        return "group-by keys differ from the reference's groups"
    have = got["s1_sum"][:count].astype(np.int32)
    bad = int(np.count_nonzero(have != sums[k]))
    return f"group-by s1_sum: {bad} of {count} groups differ" if bad else ""


def host_columns(table) -> dict:
    return {c: np.asarray(table[c]) for c in table.column_names}


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------
def serve_and_check(server: QueryServer, *, seed: int, requests: int,
                    scale: float, log=print) -> list[dict]:
    """For each of `requests` datasets (seeds seed, seed+1, ...): submit the
    join and the group-by request, drain the server, and check both answers
    against the NumPy references. Returns one record per request."""
    records = []
    for i in range(requests):
        R, S, _ = generate_tpc(WORKLOAD, scale=scale, key_bytes=KEY_BYTES,
                               payload_bytes=PAYLOAD_BYTES, seed=seed + i)
        tables = {"R": R, "S": S}
        reqs = {name: QueryRequest(qid=len(records) + j, plan=q(), tables=tables)
                for j, (name, q) in enumerate(QUERIES.items())}
        for req in reqs.values():
            server.submit(req)
        t0 = time.perf_counter()
        server.run()
        jax.block_until_ready([req.result for req in reqs.values()])
        drain_s = time.perf_counter() - t0
        Rh, Sh = host_columns(R), host_columns(S)
        for name, req in reqs.items():
            rec = {"request": req.qid, "query": name, "seed": seed + i,
                   "path": req.path, "error": req.error,
                   "exec_wall_s": req.exec_wall_s,
                   "plan_wall_s": req.plan_wall_s, "drain_wall_s": drain_s,
                   "signature": req.signature, "mismatch": ""}
            if req.result is None:
                rec["mismatch"] = f"no result: {req.error} {req.detail}"
            else:
                table, count = req.result
                got, count = host_columns(table), int(count)
                if name == "join":
                    rec["mismatch"] = check_join(got, count, reference_join(Rh, Sh))
                else:
                    rec["mismatch"] = check_groupby(got, count,
                                                    reference_groupby(Rh, Sh))
                rec["rows"] = count
                req.result = None  # release the device buffers
            req.tables = None
            records.append(rec)
            log(f"request {rec['request']} {name} seed={rec['seed']} "
                f"path={rec['path'] or '-'} error={rec['error'] or '-'} "
                f"rows={rec.get('rows', '-')} exec_wall_s={rec['exec_wall_s']} "
                f"plan_wall_s={rec['plan_wall_s']} "
                f"check={'ok' if not rec['mismatch'] else rec['mismatch']}")
    return records


def _fast_executable_text(server: QueryServer, records: list[dict]) -> dict:
    """Compiled text of each signature's fast-path executables: the served
    programs the executor compiled, and cached, for its requests' shapes."""
    from repro.engine import physical as P

    texts = {}
    for sig in dict.fromkeys(rec["signature"] for rec in records):
        if sig not in server.cache:
            continue
        entry = server.cache[sig]
        plan = entry.plan
        if entry.morsel_factor > 1:  # the fast path ran the morsel clone
            axis = P.morsel_axis(plan.root)
            plan = P.morsel_plan(plan, entry.morsel_factor,
                                 rows=entry.buckets[axis])
        texts[sig] = "\n".join(prog.compiled.as_text()
                               for prog in plan.compiled_bucketed.values())
    return texts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    from repro import compile_cache
    from repro.kernels import common as kcommon
    from repro.kernels import ops as kops
    from repro.obs import metrics

    failures = []
    if kcommon.default_interpret():
        failures.append("REPRO_PALLAS_INTERPRET forces interpret mode")
    if kops.partition_plan_impl() != "pallas":
        failures.append("REPRO_PARTITION_PLAN_IMPL forces the XLA partition arm")
    if failures:
        print("chip_smoke: " + "; ".join(failures), file=sys.stderr)
        return 1

    cache_dir = compile_cache.enable()
    cache_events = {"hits": 0, "misses": 0}
    compile_s = [0.0]

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache_events["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache_events["misses"] += 1

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s[0] += duration

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)

    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    print(f"workload: {WORKLOAD} (TPC-H Q18, SF10 extract) scale={SCALE} "
          f"seed={args.seed} requests={REQUESTS} per query")
    print(f"cuts: {'; '.join(CUTS)}")
    print(f"compile cache: {cache_dir}")

    watched = ("resilience.kernel_fallbacks", "qserve.fast_failures",
               "qserve.hard_failures", "resilience.plan_degradations")
    before = {c: metrics.counter(c).value for c in watched}
    server = QueryServer()
    t0 = time.perf_counter()
    records = serve_and_check(server, seed=args.seed, requests=REQUESTS,
                              scale=SCALE)
    total_s = time.perf_counter() - t0
    print(f"compile: backend_compile_s={compile_s[0]} "
          f"cache_hits={cache_events['hits']} "
          f"cache_misses={cache_events['misses']}")
    print(f"serve+check wall_s={total_s}")
    for name in QUERIES:
        walls = [r["exec_wall_s"] for r in records if r["query"] == name]
        print(f"{name}: exec_wall_s first (compiles) {walls[0]}, "
              f"after warm-up {walls[1:]}")

    for r in records:
        if r["error"] or r["path"] != "fast":
            failures.append(f"request {r['request']}: path={r['path']!r} "
                            f"error={r['error']!r}")
        if r["mismatch"]:
            failures.append(f"request {r['request']} ({r['query']}): "
                            f"{r['mismatch']}")
    for c in watched:
        moved = metrics.counter(c).value - before[c]
        if moved:
            failures.append(f"{c} moved by {moved}")
    for sig, entry in server.cache.items():
        text = entry.plan.explain()
        print(f"plan {sig} morsel_factor={entry.morsel_factor}:\n{text}")
        if "DEGRADED" in text:
            failures.append(f"plan {sig} is DEGRADED")
        fused = "GroupJoin[" in text
        if "GroupBy" in text or fused:
            print(f"group-by plan fused into a group-join: {fused}")
    for sig, text in _fast_executable_text(server, records).items():
        kernels = text.count("tpu_custom_call")
        print(f"fast executable {sig}: tpu_custom_call x{kernels}")
        if not kernels:
            failures.append(f"fast executable {sig} has no Pallas kernel")
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
          f"bytes_limit={stats.get('bytes_limit')}")
    for f in failures:
        print(f"FAIL: {f}")
    print(json.dumps({"ok": not failures,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(jax.devices())}}))
    return 0 if not failures else 1


if __name__ == "__main__":
    sys.exit(main())
