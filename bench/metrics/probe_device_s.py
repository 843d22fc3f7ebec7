"""probe_device_s: device seconds per traced query under the `probe` phase
scope of the served executable: match finding (hash probe, merge join,
match counts, the match list's compaction). Each busy instant of the
traced window goes to the innermost operation running then, so the five
phase metrics sum to the busy time (`bench/spans.py`)."""
import spans


def read(record):
    return spans.traced_phase_s(record, "probe")
