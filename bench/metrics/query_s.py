"""query_s: seconds per query over the window (closed loop, one client),
from the host clock: the window's query time, each query from its submit
to its synced answer, over the queries it completed."""


def read(record):
    lat = record["latencies_s"]
    return sum(lat) / len(lat) if lat else None
