"""unscoped_device_s: device seconds per traced query under no phase scope:
the executor's key masks and filters, and eager operations between
executions (input padding, the result count). Each busy instant of the
traced window goes to the innermost operation running then, so the five
phase metrics sum to the busy time (`bench/spans.py`)."""
import spans


def read(record):
    return spans.traced_phase_s(record, "unscoped")
