"""materialize_device_s: device seconds per traced query under the
`materialize` phase scope of the served executable: every move of a
non-key payload column (through a partition permutation, into the
output). Each busy instant of the traced window goes to the innermost
operation running then, so the five phase metrics sum to the busy time
(`bench/spans.py`)."""
import spans


def read(record):
    return spans.traced_phase_s(record, "materialize")
