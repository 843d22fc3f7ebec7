"""plan_audit_s: seconds the server spent sizing the signature's bytes
ticket (the peak-bytes audit of the bucketed plan and any morsel probing):
the program's `plan.audit` span summed over the run, which is set-up's
(the window's signature is cached)."""
import spans


def read(record):
    return spans.span_total_s("plan.audit")
