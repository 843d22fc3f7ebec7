"""clustered_gather_calls: executions per traced query of the
`clustered_gather` Pallas kernel (GFTR's clustered output gather), found
on the device by instruction name (`%clustered_gather.N`) inside the
harness's `bench.window` span. 0 where the served program gathers
through XLA instead; None without device operations (no TPU plane)."""
import re

import spans
import tracing

KERNEL = re.compile(r"clustered_gather(\.\d+)?$")


def calls(trace: dict) -> int:
    """Executions of the kernel inside the trace's `bench.window`."""
    lo, hi = tracing.window(trace["spans"], "bench.window")
    return sum(1 for name, start, _ in trace["ops"]
               if lo <= start < hi and KERNEL.match(spans.instruction(name)))


def read(record):
    import run  # for TRACE_DIR (run as a script, the harness is __main__)

    if not record.get("trace"):
        return None
    trace = spans.load(str(run.TRACE_DIR))
    if not trace["ops"]:
        return None
    return calls(trace) / record["trace"]["queries"]
