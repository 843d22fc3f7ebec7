"""plan_s: the planner's seconds for the cell's signature: the first
request's `plan_wall_s` (signature hash, statistics, the peak-bytes audit
and the optimizer), the span `QueryServer` keeps."""


def read(record):
    return record["plan_wall_s"]
