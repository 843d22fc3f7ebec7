"""Fused group-join: aggregate during the probe, never materialize the join.

The paper observes joins are "widely used in combination with" grouped
aggregation, yet a conventional pipeline materializes the full join result
to HBM — one gather per payload column into a `(capacity, valid_count)`
buffer sized for the worst case — and then immediately re-reads every byte
of it with a group-by. Both passes are bandwidth-bound, so the round trip
is the single largest avoidable data movement in every join+agg query.

`phj_groupjoin` removes it. It runs the same co-partition build/probe as
`phj_join` (PHJ-OM transform + match finding), but instead of compacting
matches and gathering payload columns into a join output, it folds each
matched probe row's aggregate inputs directly into a group-keyed
accumulator:

  * the probe emits (vid_r, matched) in partitioned probe order — exactly
    the `phj_join` pk_fk probe;
  * the group key and every probe-side aggregate input cost one planned
    permutation gather each (the one-permutation layer's lazy transform);
    unmatched rows are masked to KEY_SENTINEL so they can never form or
    join a group;
  * build-side inputs use the GFTR pattern: transform once (one n_build
    permutation gather), then ONE clustered probe-length gather through
    the matched virtual IDs — n_probe rows, not `capacity` rows, and no
    second read;
  * the accumulator is the group-by machinery itself (`group_aggregate`),
    running over the probe-length arrays: scatter-free (one-hot-matmul
    tile partials / segmented reductions — DESIGN.md §2), exact for any
    key distribution with the always-exact 'sort'/'partition_hash'
    strategies.

The joined row is never written: no compaction, no capacity-sized
buffers, no per-payload materialization gathers, no re-read. The cost
model (`planner.predict_groupjoin_time`) prices this as probe cost +
accumulate cost with a zero materialization term.

Scope: inner pk_fk joins (build keys unique). m:n group-joins would need
multiplicity-weighted accumulation and are out of scope; the engine's
fusion pass only fires on provably pk_fk joins.

Static-shape contract: `num_groups` is the accumulator capacity; output is
(Table(group_key + f"{col}_{op}" columns), valid_count), padded with
KEY_SENTINEL — identical to `group_aggregate`. Groups beyond capacity are
dropped; `groupjoin_checked` escalates partition bits (build-block
overflow, the `phj_join_checked` policy) and then accumulator capacity
(exact distinct-group count) so the fused result is always exact.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import primitives as prim
from .groupby import AGG_OPS, group_aggregate
from .hash_join import (BUILD_BLOCK, _digits, _nonempty, blocked_partitions,
                        build_blocks, choose_partition_bits,
                        escalate_partition_bits, phj_overflowed, probe_pk_fk)
from .table import KEY_SENTINEL, Table


def _value_blocks(vals_part: jax.Array, off: jax.Array, sz: jax.Array,
                  cap: int) -> jax.Array:
    """(P, cap) float32 value blocks aligned with `build_blocks`' key blocks
    (same padding geometry, 0.0 fill)."""
    blocks, _, _ = blocked_partitions(vals_part.astype(jnp.float32), off, sz,
                                      cap, 0.0)
    return blocks


def phj_groupjoin(
    R: Table,
    S: Table,
    *,
    key: str = "k",
    group_key: str,
    aggs: dict[str, str],
    num_groups: int,
    agg_strategy: str = "sort",
    build_block: int = BUILD_BLOCK,
    partition_bits: int | None = None,
    hash_keys: bool = True,
    probe_chunk: int = 8192,
    probe_impl: str = "xla",  # "xla" | "pallas" (fused probe+accumulate kernel)
    agg_kw: dict | None = None,
):
    """Fused pk_fk join + grouped aggregation. Returns (Table, valid_count).

    `group_key` must be a probe-side (S) column — the join key itself is
    allowed. `aggs` maps a column of either relation to an op in
    sum/count/min/max/mean; output columns are named f"{col}_{op}".

    `probe_impl="pallas"` runs the probe+accumulate Pallas kernel (per-tile
    one-hot-matmul partials + segmented combine — the §2 mapping of the
    GPU's shared-memory hash accumulator; sum/count/mean, integer group
    keys). The "xla" path supports the full op set and any `agg_strategy`
    accepted by `group_aggregate`.
    """
    if group_key not in S.column_names:
        raise ValueError(
            f"group_key {group_key!r} must be a probe-side column "
            f"(have {S.column_names}); build-side group keys would need the "
            "matched row materialized — the movement this operator removes")
    for col, op in aggs.items():
        if op not in AGG_OPS:
            raise ValueError(f"unknown agg op {op!r} for {col!r}")
        if col not in S.column_names and col not in R.column_names:
            raise ValueError(f"agg column {col!r} in neither relation")

    R = _nonempty(R, key)
    S = _nonempty(S, key)
    p_bits = (partition_bits if partition_bits is not None
              else choose_partition_bits(R.num_rows, build_block))
    P = 1 << p_bits

    with prim.phase("partition"):
        dig_r = _digits(R[key], p_bits, hash_keys)
        dig_s = _digits(S[key], p_bits, hash_keys)
        # P + 1 partitions: sentinel rows flood the extra one (see
        # hash_join._digits) and never reach a build block or probe pass
        perm_r, off_r, sz_r = prim.plan_partition_permutation(dig_r, P + 1)
        perm_s, off_s, sz_s = prim.plan_partition_permutation(dig_s, P + 1)
        off_r, sz_r = off_r[:P], sz_r[:P]
        off_s, sz_s = off_s[:P], sz_s[:P]

        kr = prim.apply_permutation(perm_r, R[key])
        ks, dig_s_part = prim.apply_permutation(perm_s, S[key], dig_s)
        bkeys, _, _ = build_blocks(kr, off_r, sz_r, build_block)

    # Probe-side columns reach partitioned order by the one-permutation
    # layer's lazy transform: exactly one planned-permutation gather per
    # column the aggregation actually reads, computed on demand and shared
    # between the group key and an agg on the same column.
    probe_part: dict[str, jax.Array] = {key: ks}

    def probe_col(col):
        if col not in probe_part:
            probe_part[col] = prim.apply_permutation(perm_s, S[col])
        return probe_part[col]

    with prim.phase("partition"):  # the group key is a key column
        gk = probe_col(group_key)

    if probe_impl == "pallas":
        return _groupjoin_pallas(R, S, key, aggs, num_groups, bkeys, off_r,
                                 sz_r, perm_r, probe_col, gk, off_s, sz_s,
                                 group_key)

    with prim.phase("probe"):
        vid_r, matched = probe_pk_fk(bkeys, off_r, ks, dig_s_part, probe_chunk)
        gk_masked = jnp.where(matched, gk, jnp.asarray(KEY_SENTINEL, gk.dtype))

    # Per-row aggregate inputs in partitioned probe order — the rows the
    # accumulator consumes directly; the joined row is never assembled.
    cols = {group_key: gk_masked}
    with prim.phase("materialize"):
        for col, op in aggs.items():
            if col in cols:
                continue  # aggregating the group key: reuse the masked column
            if op == "count":
                # counts ignore values on every strategy; skip any fetch
                cols[col] = jnp.zeros(ks.shape, jnp.int32)
            elif col in S.column_names:
                cols[col] = probe_col(col)  # the column's ONE lazy-transform gather
            else:
                # build-side input, GFTR pattern: transform once (one n_build
                # permutation gather), then ONE clustered probe-length gather
                # through the matched virtual IDs (clustered within
                # co-partitions — the same access shape as phj_join's ID_R)
                tr = prim.apply_permutation(perm_r, R[col])
                cols[col] = prim.clustered_gather(
                    tr, jnp.where(matched, vid_r, -1))

    return group_aggregate(Table(cols), key=group_key, aggs=aggs,
                           num_groups=num_groups, strategy=agg_strategy,
                           **(agg_kw or {}))


def _groupjoin_pallas(R, S, key, aggs, num_groups, bkeys, off_r, sz_r, perm_r,
                      probe_col, gk, off_s, sz_s, group_key):
    """Probe+accumulate via the Pallas kernel: ONE fused pass — match
    finding, in-VMEM build-value fetch, and tile-local partial aggregation
    for every aggregate column together — then one sorted segmented
    combine. sum/count/mean over integer group keys."""
    from repro.kernels import ops as kops

    for col, op in aggs.items():
        if op not in ("sum", "mean", "count"):
            raise ValueError(
                f"groupjoin probe_impl='pallas' supports sum/mean/count, got "
                f"{op!r} for {col!r} (use the xla path for min/max)")
    if not jnp.issubdtype(gk.dtype, jnp.integer):
        raise ValueError("groupjoin probe_impl='pallas' needs integer group keys")

    # Stack the sum-bearing columns per side; every column rides the single
    # probe kernel pass (col_sides maps output order -> side + within-side
    # index), and probe columns cost one lazy-transform gather each.
    ks = probe_col(key)
    sum_cols = [(col, op) for col, op in aggs.items() if op != "count"]
    col_sides, pv_cols, bv_cols = [], [], []
    with prim.phase("materialize"):
        for col, _ in sum_cols:
            if col in S.column_names:
                col_sides.append(("probe", len(pv_cols)))
                pv_cols.append(probe_col(col).astype(jnp.float32))
            else:
                vr_part = prim.apply_permutation(perm_r, R[col])
                col_sides.append(("build", len(bv_cols)))
                bv_cols.append(_value_blocks(vr_part, off_r, sz_r,
                                             bkeys.shape[1]))
        bv = jnp.stack(bv_cols, axis=1) if bv_cols else None
        pv = jnp.stack(pv_cols) if pv_cols else None
    # one fused pass finds the matches and accumulates them: its time counts
    # as match finding
    with prim.phase("probe"):
        gkeys, sums, gcounts, count = kops.groupjoin_probe_agg(
            bkeys, bv, off_r, ks, gk, pv, off_s, sz_s, num_groups,
            col_sides=tuple(col_sides), impl="pallas")

    out: dict[str, jax.Array] = {}
    for (col, op), s in zip(sum_cols, sums):
        out[f"{col}_{op}"] = s
    with prim.phase("aggregate"):
        for col, op in aggs.items():
            if op == "count":
                out[f"{col}_{op}"] = gcounts.astype(jnp.int32)
            elif op == "mean":
                out[f"{col}_{op}"] = out[f"{col}_{op}"] / jnp.maximum(
                    gcounts.astype(jnp.float32), 1.0)
    return Table({group_key: gkeys, **out}), count


# ---------------------------------------------------------------------------
# Overflow-checked driver (bits-then-capacity escalation)
# ---------------------------------------------------------------------------
def groupjoin_required_groups(S: Table, *, key: str = "k", group_key: str,
                              agg_strategy: str = "sort") -> int:
    """EXACT lower bound on the accumulator capacity the fused aggregation
    needs: the distinct count of probe-side group keys over rows whose join
    key is valid (matching only removes rows) — or, for the 'scatter'
    strategy, the dense key DOMAIN (max valid group key + 1), since scatter
    indexes the accumulator by key value and drops out-of-domain keys.
    Device-side sort/max + scalar transfer; the capacity analogue of
    `phj_overflowed`'s histogram."""
    if S.num_rows == 0:
        return 0
    gk = S[group_key]
    valid = S[key] != jnp.asarray(KEY_SENTINEL, S[key].dtype)
    sentinel = jnp.asarray(KEY_SENTINEL, gk.dtype)
    if agg_strategy == "scatter":
        return int(jnp.max(jnp.where(valid, gk, sentinel))) + 1
    sk = jnp.sort(jnp.where(valid, gk, sentinel))
    present = sk != sentinel
    boundary = jnp.concatenate([present[:1], (sk[1:] != sk[:-1]) & present[1:]])
    return int(jnp.sum(boundary.astype(jnp.int32)))


def groupjoin_overflowed(R: Table, S: Table, *, key: str = "k",
                         group_key: str, num_groups: int,
                         build_block: int = BUILD_BLOCK,
                         partition_bits: int | None = None,
                         hash_keys: bool = True,
                         agg_strategy: str = "sort"):
    """Host-side check of both static capacities the fused path pads to:
    would any build co-partition exceed its block (more partition bits can
    fix it), and does the accumulator cover every possible group (only a
    larger capacity can). Returns (build_overflow, p_bits, group_overflow,
    required_groups)."""
    build_ovf, p_bits = phj_overflowed(R, key=key, build_block=build_block,
                                       partition_bits=partition_bits,
                                       hash_keys=hash_keys)
    required = groupjoin_required_groups(S, key=key, group_key=group_key,
                                         agg_strategy=agg_strategy)
    return build_ovf, p_bits, required > num_groups, required


def groupjoin_checked(R: Table, S: Table, *, key: str = "k", group_key: str,
                      aggs: dict[str, str], num_groups: int,
                      max_extra_bits: int = 4,
                      build_block: int = BUILD_BLOCK, max_attempts: int = 8,
                      with_report: bool = False, **kw):
    """phj_groupjoin on the resilience ladder (DESIGN.md §13), covering
    both static capacities the fused path pads to: FIRST add partition
    bits while a build co-partition overflows its padded block, THEN grow
    the accumulator when `num_groups` would drop groups — to the exact
    distinct-group count (or the dense key domain for the 'scatter'
    strategy, which indexes the accumulator by key value). Both checks are
    cheap host-side reductions; the re-run uses strictly larger static
    shapes, so the result is exact. Bounded: `EscalationExhausted` instead
    of a silent lossy run.

    `with_report=True` additionally returns the `EscalationReport`."""
    from repro.resilience import EscalationStep, Ladder

    hash_keys = kw.get("hash_keys", True)
    agg_strategy = kw.get("agg_strategy", "sort")
    base_bits = kw.pop("partition_bits", None)
    if base_bits is None:
        base_bits = choose_partition_bits(R.num_rows, build_block)
    knobs = {"partition_bits": base_bits, "num_groups": num_groups}

    def check(kn):
        build_ovf, _, group_ovf, required = groupjoin_overflowed(
            R, S, key=key, group_key=group_key, num_groups=kn["num_groups"],
            build_block=build_block, partition_bits=kn["partition_bits"],
            hash_keys=hash_keys, agg_strategy=agg_strategy)
        parts = []
        if build_ovf:
            parts.append(f"build partition > {build_block} rows")
        if group_ovf:
            parts.append(f"{required} groups > capacity {kn['num_groups']}")
        return (not parts, "; ".join(parts),
                {"build_ovf": build_ovf, "required": required})

    def grow_bits(kn, diag):
        # yields to the capacity rung when the diagnosis shows a pure
        # accumulator overflow (more fan-out cannot create capacity)
        if kn["partition_bits"] >= 20:
            return None
        if diag is not None and not diag["build_ovf"] \
                and diag["required"] > kn["num_groups"]:
            return None
        return {**kn, "partition_bits": kn["partition_bits"] + 1}

    def grow_capacity(kn, diag):
        required = diag["required"] if diag else 0
        if diag is not None and diag["build_ovf"] \
                and required <= kn["num_groups"]:
            return None  # capacity cannot fix a build-block overflow
        if required > kn["num_groups"]:
            # lane-friendly growth, mirroring the engine's capacity rounding
            target = -(-required // 64) * 64
        else:  # forced overflow with nothing actually wrong: double
            target = max(64, kn["num_groups"] * 2)
        return {**kn, "num_groups": target}

    ladder = Ladder("groupjoin", [
        EscalationStep("partition_bits", grow_bits, max_times=max_extra_bits),
        EscalationStep("num_groups", grow_capacity, max_times=3),
    ], max_attempts=max_attempts)
    report = ladder.resolve(knobs, check)
    kn = report.final_knobs
    out = phj_groupjoin(R, S, key=key, group_key=group_key, aggs=aggs,
                        num_groups=kn["num_groups"], build_block=build_block,
                        partition_bits=kn["partition_bits"], **kw)
    return (out, report) if with_report else out
