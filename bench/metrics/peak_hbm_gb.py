"""peak_hbm_gb: the device's own peak of bytes in use over the run, up to
the end of the window (`memory_stats()["peak_bytes_in_use"]`), in GB
(10^9 bytes)."""


def read(record):
    return record["peak_bytes"] / 1e9
