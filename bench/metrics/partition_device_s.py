"""partition_device_s: device seconds per traced query under the
`partition` phase scope of the served executable: the key-side
transforms (digits, histograms, ranks, the permutation, key sorts). Each
busy instant of the traced window goes to the innermost operation
running then, so the five phase metrics sum to the busy time
(`bench/spans.py`)."""
import spans


def read(record):
    return spans.traced_phase_s(record, "partition")
