"""`python -m repro.serve --chaos` — chaos/soak gate for the query server.

Drives the mixed-query soak (serve/chaos.py) under every fault-grammar
family, writes the scoreboard to BENCH_serve.json (p50/p99 latency +
throughput baseline, per-family blast-radius reports, degradation
counters), and exits non-zero if any delivered result diverged from its
fault-free oracle or any blast-radius / counter-consistency assertion
failed.

Usage: python -m repro.serve --chaos [--smoke] [--out PATH]
  --smoke   CI scale (<= 48 queries per family instead of 200)
  --out     output path (default BENCH_serve.json)
"""
from __future__ import annotations

import json
import sys


def main(argv: list[str]) -> int:
    if "--chaos" not in argv:
        print(__doc__)
        return 0 if argv in ([], ["--help"]) else 2
    out = "BENCH_serve.json"
    if "--out" in argv:
        out = argv[argv.index("--out") + 1]
    from repro import compile_cache
    from repro.serve.chaos import run_chaos

    compile_cache.enable()
    report = run_chaos(smoke="--smoke" in argv)
    with open(out, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
    print(json.dumps({"ok": report["ok"], "failures": report["failures"],
                      "baseline": {k: report["baseline"][k] for k in
                                   ("p50_s", "p99_s", "throughput_qps")},
                      "wrote": out}, indent=2, sort_keys=True))
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
