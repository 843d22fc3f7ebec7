"""device_idle_share: the share (%) of the traced window in which no
operation ran on the device: 1 - busy / window."""


def read(record):
    trace = record["trace"]
    if not trace or trace["busy_s"] <= 0:  # no device op found
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
