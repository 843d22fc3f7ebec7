"""host_dispatch_ms: the server's host work per window query, in
milliseconds: the signature hash (`qserve.signature`), the eager padding
of the inputs (`qserve.pad`) and handing the executable to the device
(`qserve.dispatch`), the program's spans, averaged over the window's
queries (their last occurrences; set-up's first query, which compiles,
comes before them)."""
import spans


def read(record):
    mean = spans.window_mean_s(("qserve.signature", "qserve.pad", "qserve.dispatch"),
                               len(record["latencies_s"]))
    return None if mean is None else 1e3 * mean
