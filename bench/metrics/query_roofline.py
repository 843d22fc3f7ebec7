"""query_roofline: the query's least HBM bytes at the chip's peak
bandwidth, as a share (%) of the device's busy time per traced query.
The bytes count each input column the query needs read once and each
output column written once (`plans.least_bytes`), so the share reads the
same work whatever implements it and cannot pass 100 %."""


def read(record):
    trace = record["trace"]
    if not trace or trace["busy_s"] <= 0:
        return None
    least_s = record["least_bytes"] / record["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (trace["busy_s"] / trace["queries"])
