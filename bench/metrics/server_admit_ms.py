"""server_admit_ms: the server's own time per query, in milliseconds:
the harness's submit-to-answer wall minus the request's `exec_wall_s`
(the server's span around planning-cache lookup, admission and the tick
loop, outside the execution), averaged over the window's queries."""


def read(record):
    pairs = list(zip(record["latencies_s"], record["exec_walls_s"]))
    if not pairs:
        return None
    return 1e3 * sum(lat - ex for lat, ex in pairs) / len(pairs)
