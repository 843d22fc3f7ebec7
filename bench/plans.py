"""A traffic mix's query, read from its data file.

A traffic file (`bench/traffic/<mix>.json`) gives its query as a list of
steps, applied in order to the plan built so far:

  ["scan", "S"]                                           start from table S
  ["join", {"table": "R", "key": "k"}]                    equi-join to R on k
  ["group_by", {"key": "k", "aggs": {"s1_lo": "sum"}}]    one row per key

A join is an inner join on one key and names no kind: the planner
decides from the keys' statistics (mode "auto") whether it is PK-FK (one
side's key unique) or m:n.

`build` turns the steps into the program's logical plan (the query a
client submits); `bench/reference.py` evaluates the same steps in NumPy.
`least_bytes` is the HBM traffic no implementation can avoid: every input
column the query needs, read once at its true row count, and every output
column written once at the returned row count.
"""
from __future__ import annotations


def build(steps: list):
    """The logical plan (`repro.engine.logical`) of a traffic's steps."""
    from repro.engine.logical import scan

    (op, table), rest = steps[0], steps[1:]
    if op != "scan":
        raise ValueError(f"a plan starts with a scan, not {op!r}")
    plan = scan(table)
    for op, arg in rest:
        if op == "join":
            plan = plan.join(scan(arg["table"]), key=arg["key"])
        elif op == "group_by":
            plan = plan.group_by(arg["key"], dict(arg["aggs"]))
        else:
            raise ValueError(f"unknown plan step {op!r}")
    return plan


def output_columns(steps: list, schemas: dict) -> list[str]:
    """Column names of the result, given {table: [column, ...]}."""
    cols = list(schemas[steps[0][1]])
    for op, arg in steps[1:]:
        if op == "join":
            cols += [c for c in schemas[arg["table"]] if c not in cols]
        elif op == "group_by":
            cols = [arg["key"]] + [f"{c}_{a}" for c, a in sorted(arg["aggs"].items())]
    return cols


def needed_columns(steps: list, schemas: dict) -> dict[str, set]:
    """{table: columns the query must read}: the columns that reach the
    output or decide which rows do."""
    # walk backwards from the output: what each step needs from its input
    need = set(output_columns(steps, schemas))
    reads: dict[str, set] = {}
    for op, arg in reversed(steps[1:]):
        if op == "join":
            right = set(schemas[arg["table"]])
            reads[arg["table"]] = (need & right) | {arg["key"]}
            need = (need - right) | {arg["key"]}
        elif op == "group_by":
            need = {arg["key"]} | set(arg["aggs"])
    reads[steps[0][1]] = need & set(schemas[steps[0][1]])
    return reads


def least_bytes(steps: list, rows: dict, schemas: dict, out_rows: int,
                itemsize: int = 4) -> int:
    """Least HBM bytes of one query: needed input columns read once at
    their true `rows`, output columns written once at `out_rows`. Every
    column is `itemsize` bytes wide."""
    read = sum(rows[t] * len(cols)
               for t, cols in needed_columns(steps, schemas).items())
    write = out_rows * len(output_columns(steps, schemas))
    return (read + write) * itemsize
