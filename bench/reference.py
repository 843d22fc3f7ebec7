"""The plain reference: a traffic's query evaluated in NumPy, and the
comparison that decides `correct`.

`evaluate` runs the same steps as `plans.build` over host copies of the
tables. A join is an inner equi-join on one key: where the build keys are
unique (PK-FK), a direct-address lookup of each probe key; otherwise
(m:n) each probe row paired with every build row of its key, found by
binary search in the build keys sorted once, and expanded in blocks of
probe rows so that host memory stays near the answer's own size. A
group-by is exact integer sums per key. Keys are dense, as the generator
makes them: none is negative or above 4 times the rows (a join over
other keys takes the binary search). Integer results wrap as int32 does,
the precision the configurations state. It imports nothing of the
program under test.

`compare` checks one served answer against the reference's rows as a
multiset, since no operator promises an order: each row is hashed over
all its columns into 64 bits and the sorted hashes are compared. A wrong
row passes only if its hash equals that of a missing row (odds about
2^-64 per row).

`control` is the reference with one guarantee broken: every non-key value
of its result is rounded through bfloat16, the precision a default TPU
matmul gives the one-hot gathers and segment sums, the step a later change
would be tempted to take. The comparison has to refuse it.
"""
from __future__ import annotations

import numpy as np

import plans


def _dense(keys: np.ndarray) -> int:
    """The largest key, once it is checked that the keys are dense."""
    lo, hi = int(keys.min(initial=0)), int(keys.max(initial=0))
    if lo < 0 or hi >= 4 * len(keys) + 1024:
        raise ValueError("the reference needs dense keys")
    return hi


def _lookup(build: np.ndarray, probe: np.ndarray):
    """(hit mask, build row of each hit) for unique dense build keys; None
    where a build key repeats or the keys are not dense."""
    try:
        hi = _dense(build)
    except ValueError:
        return None
    slot = np.full(hi + 1, -1, np.int64)
    slot[build] = np.arange(len(build))
    if np.count_nonzero(slot >= 0) != len(build):
        return None
    inside = (probe >= 0) & (probe <= hi)
    row = np.full(len(probe), -1, np.int64)
    row[inside] = slot[probe[inside]]
    return row >= 0, row[row >= 0]


PROBE_BLOCK = 1 << 20  # probe rows expanded at a time by `_expand`


def _expand(cols: dict, right: dict, key: str) -> dict:
    """The m:n inner join: every row of `cols` (the probe side) once for
    each row of `right` (the build side) with an equal `key`, the build
    side's other columns beside it."""
    order = np.argsort(right[key], kind="stable")
    keys = right[key][order]
    lo = np.searchsorted(keys, cols[key], "left")
    count = np.searchsorted(keys, cols[key], "right") - lo
    end = np.cumsum(count)
    total = int(end[-1]) if len(end) else 0
    extra = [c for c in right if c not in cols]
    out = {c: np.empty(total, v.dtype) for c, v in cols.items()}
    out.update({c: np.empty(total, right[c].dtype) for c in extra})
    for s in range(0, len(count), PROBE_BLOCK):
        e = min(s + PROBE_BLOCK, len(count))
        a, b = int(end[s] - count[s]), int(end[e - 1])
        prow = np.repeat(np.arange(s, e), count[s:e])
        # output row i of probe row p is its (i - first output row of p)-th
        # match, build row order[lo[p] + that]
        brow = order[lo[prow] + np.arange(a, b) - (end[prow] - count[prow])]
        for c, v in cols.items():
            out[c][a:b] = v[prow]
        for c in extra:
            out[c][a:b] = right[c][brow]
    return out


def _int32_sums(keys: np.ndarray, values: np.ndarray, groups: int) -> np.ndarray:
    """Exact per-key sums of int32 values, wrapped to int32: in float64
    where no sum can reach 2^53, else each 16-bit half apart."""
    top = int(np.abs(values.astype(np.int64)).max(initial=0))
    if top * len(values) < 2**53:  # one float64 sum is exact
        return np.bincount(keys, weights=values, minlength=groups).astype(
            np.int64).astype(np.int32)
    v = values.astype(np.int64)
    lo = np.bincount(keys, weights=v & 0xFFFF, minlength=groups)
    hi = np.bincount(keys, weights=v >> 16, minlength=groups)
    total = hi.astype(np.int64) * 65536 + lo.astype(np.int64)
    return total.astype(np.int32)  # two's-complement wrap, as int32 adds


def _groups(keys: np.ndarray):
    """(distinct keys in order, group index of each row)."""
    present = np.bincount(keys, minlength=_dense(keys) + 1) > 0
    rank = np.cumsum(present) - 1
    return np.flatnonzero(present), rank[keys]


def _group_by(cols: dict, key: str, aggs: dict) -> dict:
    uniq, inv = _groups(cols[key])
    out = {key: uniq.astype(cols[key].dtype)}
    for c, op in sorted(aggs.items()):
        if op != "sum":
            raise ValueError(f"the reference has no aggregate {op!r}")
        out[f"{c}_{op}"] = _int32_sums(inv, cols[c], len(uniq))
    return out


def evaluate(steps: list, tables: dict) -> dict:
    """The query's result rows, {column: array}, in no particular order.
    Columns the query does not read are dropped first."""
    need = plans.needed_columns(steps, {n: list(t) for n, t in tables.items()})
    tables = {n: {c: v for c, v in t.items() if c in need.get(n, ())}
              for n, t in tables.items()}
    cols = dict(tables[steps[0][1]])
    for op, arg in steps[1:]:
        if op == "join":
            right = tables[arg["table"]]
            found = _lookup(right[arg["key"]], cols[arg["key"]])
            if found is None:
                cols = _expand(cols, right, arg["key"])
                continue
            hit, row = found
            if not hit.all():
                cols = {c: v[hit] for c, v in cols.items()}
            cols.update({c: v[row] for c, v in right.items() if c not in cols})
        elif op == "group_by":
            cols = _group_by(cols, arg["key"], arg["aggs"])
        else:
            raise ValueError(f"unknown plan step {op!r}")
    return cols


def key_columns(steps: list) -> set:
    """Columns that carry keys, which the control leaves exact."""
    return {arg["key"] for op, arg in steps[1:] if op in ("join", "group_by")}


def to_bfloat16(v: np.ndarray) -> np.ndarray:
    """Integers rounded to the nearest bfloat16 (ties to even), as int32."""
    bits = v.astype(np.float32).view(np.uint32).astype(np.uint64)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return bits.astype(np.uint32).view(np.float32).astype(np.int64).astype(np.int32)


def control(steps: list, tables: dict) -> dict:
    """The reference's rows with every non-key value rounded through
    bfloat16: the control that the comparison has to refuse."""
    exact = evaluate(steps, tables)
    keys = key_columns(steps)
    return {c: v if c in keys else to_bfloat16(v) for c, v in exact.items()}


_MIX1, _MIX2 = np.uint64(0xBF58476D1CE4E5B9), np.uint64(0x94D049BB133111EB)
_STEP = np.uint64(0x9E3779B97F4A7C15)


def row_hashes(cols: dict) -> np.ndarray:
    """One 64-bit hash per row over every column, in sorted column order;
    equal rows hash equal whatever the integer dtype."""
    names = sorted(cols)
    h = np.zeros(len(cols[names[0]]), np.uint64)
    with np.errstate(over="ignore"):
        for name in names:
            h = h * _STEP + cols[name].astype(np.int64).view(np.uint64)
        h ^= h >> np.uint64(30)
        h *= _MIX1
        h ^= h >> np.uint64(27)
        h *= _MIX2
        h ^= h >> np.uint64(31)
    return h


class Expected:
    """The reference's result, held as sorted row hashes for comparing
    many answers."""

    def __init__(self, rows: dict):
        self.columns = sorted(rows)
        self.rows = len(rows[self.columns[0]])
        self.hashes = np.sort(row_hashes(rows))

    def compare(self, got: dict, count: int) -> dict:
        """{"rows_off": rows of the answer or the reference that the other
        lacks, counted with multiplicity (0 when they agree as
        multisets), "count_gap": |answer rows - reference rows|}."""
        gap = abs(int(count) - self.rows)
        if sorted(got) != self.columns:
            return {"rows_off": max(int(count), self.rows), "count_gap": gap}
        have = np.sort(row_hashes({c: np.asarray(v)[:count] for c, v in got.items()}))
        if np.array_equal(have, self.hashes):
            return {"rows_off": 0, "count_gap": 0}
        ua, ca = np.unique(have, return_counts=True)
        ub, cb = np.unique(self.hashes, return_counts=True)
        _, ia, ib = np.intersect1d(ua, ub, assume_unique=True,
                                   return_indices=True)
        shared = int(np.minimum(ca[ia], cb[ib]).sum())
        return {"rows_off": max(int(count), self.rows) - shared,
                "count_gap": gap}
