"""Grouped aggregations [extension-per-assigned-title].

The assigned paper title ("Efficiently Processing Joins and Grouped
Aggregations on GPUs") and the calibration band cover group-by kernels; the
provided text covers only joins, so this module applies the same design
principles to grouped aggregation:

  * scatter-based aggregation (atomicAdd on GPUs, `segment_sum` scatter here)
    is the unclustered-access baseline — only viable for dense key domains;
  * sort-based aggregation transforms (sorts) the rows first so the reduce is
    over contiguous runs — sequential access, the GFTR insight;
  * two-phase block aggregation ("partition_hash") pre-aggregates each
    VMEM-resident tile with a one-hot matmul reduction (MXU work — the TPU
    analogue of a shared-memory hash table per thread block), then combines
    the per-tile partials with a sorted pass. Correct for *any* key
    distribution (heavy hitters are reduced tile-locally first, the same way
    GPU shared-memory pre-aggregation absorbs skew);
  * partition-based aggregation ("partition", DESIGN.md §8) radix-partitions
    rows on hashed key bits until each partition's group set fits a
    VMEM-resident block, then aggregates every partition independently —
    no global sort, no cross-partition combine, since a group lives in
    exactly one partition. The paper's third group-by algorithm, ideal for
    high group cardinalities;
  * wide payloads follow Algorithm 1 with the one-permutation refinement:
    the sort/partition is planned ONCE (`primitives.plan_sort_permutation` /
    `plan_partition_permutation`) and every payload column is materialized
    with a single `apply_permutation` gather.

All APIs are static-shape: `num_groups` is a capacity; outputs are
(keys[num_groups], aggs[num_groups], valid_count), padded with KEY_SENTINEL.

Supported aggregations: sum, count, min, max, mean.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import primitives as prim
from .hash_join import _nonempty, hash32
from .table import KEY_SENTINEL, Table

AGG_OPS = ("sum", "count", "min", "max", "mean")


def _seg_reduce(op, vals, gid, num_segments):
    if op in ("sum", "mean"):
        return jax.ops.segment_sum(vals, gid, num_segments=num_segments)
    if op == "count":
        return jax.ops.segment_sum(jnp.ones_like(vals, jnp.int32), gid, num_segments=num_segments)
    if op == "min":
        return jax.ops.segment_min(vals, gid, num_segments=num_segments)
    if op == "max":
        return jax.ops.segment_max(vals, gid, num_segments=num_segments)
    raise ValueError(op)


def _finalize(op, acc, counts):
    if op == "mean":
        return acc / jnp.maximum(counts, 1).astype(acc.dtype)
    return acc


# Partial-aggregation plumbing: op -> (tile partial op, combine op)
_PARTIAL = {
    "sum": ("sum", "sum"),
    "count": ("count", "sum"),
    "mean": ("sum", "sum"),  # + count partial, finalized at the end
    "min": ("min", "min"),
    "max": ("max", "max"),
}


# ---------------------------------------------------------------------------
# Sort-based (transform-first; GFTR analogue)
# ---------------------------------------------------------------------------
def groupby_sort(
    table: Table,
    *,
    key: str = "k",
    aggs: dict[str, str],
    num_groups: int,
):
    """Sort rows by key, detect run boundaries, segment-reduce.

    One-permutation materialization (DESIGN.md §8): the key sort is planned
    once and each payload column is transformed with a single
    `apply_permutation` gather — Algorithm 1's lazy transform without the
    per-column re-sort it used to cost.
    Returns (Table(key + agg columns), valid_count)."""
    table = _nonempty(table, key)  # zero rows -> one all-sentinel row
    keys = table[key]
    with prim.phase("partition"):
        sk, perm = prim.plan_sort_permutation(keys)
    with prim.phase("aggregate"):
        boundary = jnp.concatenate([jnp.ones((1,), bool), sk[1:] != sk[:-1]])
        boundary &= sk != KEY_SENTINEL
        valid_row = sk != KEY_SENTINEL
        gid = jnp.cumsum(boundary.astype(jnp.int32)) - 1  # dense, sorted group ids
        n_found = gid[-1] + 1
        gid = jnp.where(valid_row, gid, num_groups)
        gid_cap = jnp.where(gid < num_groups, gid, num_groups)  # overflow -> dropped

        out_keys = jnp.full((num_groups + 1,), KEY_SENTINEL, keys.dtype)
        out_keys = out_keys.at[gid_cap].set(jnp.where(valid_row, sk, KEY_SENTINEL), mode="drop")
        counts = jax.ops.segment_sum(
            valid_row.astype(jnp.int32), gid_cap, num_segments=num_groups + 1
        )

    cols = {key: out_keys[:num_groups]}
    for col, op in aggs.items():
        with prim.phase("materialize"):
            tv = prim.apply_permutation(perm, table[col])  # one gather per column
        with prim.phase("aggregate"):
            acc = _seg_reduce(op, jnp.where(valid_row, tv, 0) if op in ("sum", "mean") else tv,
                              gid_cap, num_groups + 1)
            cols[f"{col}_{op}"] = _finalize(op, acc, counts)[:num_groups]
    count = jnp.minimum(n_found, num_groups)
    return Table(cols), count


# ---------------------------------------------------------------------------
# Two-phase block aggregation (MXU one-hot partials + sorted combine)
# ---------------------------------------------------------------------------
def _block_local_groups(kp):
    """Block-local grouping core shared by the tile and partition paths: for
    (T, B) key blocks (KEY_SENTINEL = invalid slot), sort each block locally
    — the VMEM-resident analogue of a per-thread-block hash table — and
    assign dense local group ids.

    Returns (ks, order, valid, bnd, lgid): locally sorted keys, the per-block
    sort order (to align payload blocks), validity, run boundaries, and local
    group ids (invalid rows -> B, so they drop out of one-hot/segment
    reductions)."""
    block = kp.shape[1]
    order = jnp.argsort(kp, axis=1, stable=True)
    ks = jnp.take_along_axis(kp, order, axis=1)
    valid = ks != KEY_SENTINEL
    bnd = jnp.concatenate([jnp.ones((ks.shape[0], 1), bool), ks[:, 1:] != ks[:, :-1]], axis=1)
    bnd &= valid
    lgid = jnp.cumsum(bnd.astype(jnp.int32), axis=1) - 1
    lgid = jnp.where(valid, lgid, block)
    return ks, order, valid, bnd, lgid


def _tile_partials(keys, cols_ops, block):
    """Phase 1: per tile of `block` rows, aggregate duplicates tile-locally.

    Returns (partial_keys[npad], partial_counts[npad], {name: partial[npad]})
    where slots without a group carry KEY_SENTINEL. Each tile contributes its
    distinct keys once — heavy hitters collapse block-fold per pass."""
    n = keys.shape[0]
    n_pad = -n % block
    with prim.phase("partition"):
        kp = jnp.pad(keys, (0, n_pad), constant_values=KEY_SENTINEL).reshape(-1, block)
        ks, order, valid, bnd, lgid = _block_local_groups(kp)
    with prim.phase("aggregate"):
        oh = jax.nn.one_hot(lgid, block, dtype=jnp.float32)  # (T, block, block)

        pcounts = jnp.einsum("tbg->tg", oh)
        # group g's key: scatter run-head keys into slot g (run heads are unique per tile)
        T = ks.shape[0]
        pkeys = (
            jnp.full((T, block + 1), KEY_SENTINEL, keys.dtype)
            .at[jnp.arange(T)[:, None], jnp.where(bnd, lgid, block)]
            .set(ks, mode="drop")[:, :block]
        )

    partials = {}
    for name, (vals, pop) in cols_ops.items():
        with prim.phase("materialize"):
            vp = jnp.pad(vals, (0, n_pad)).reshape(-1, block)
            vs = jnp.take_along_axis(vp, order, axis=1).astype(jnp.float32)
        with prim.phase("aggregate"):
            if pop == "sum":  # HIGHEST: a TPU's default f32 matmul rounds to bf16
                acc = jnp.einsum("tb,tbg->tg", jnp.where(valid, vs, 0.0), oh,
                                 precision=jax.lax.Precision.HIGHEST)
            elif pop == "count":
                acc = pcounts
            elif pop in ("min", "max"):
                fill = jnp.float32(jnp.finfo(jnp.float32).max if pop == "min"
                                   else jnp.finfo(jnp.float32).min)
                masked = jnp.where(oh > 0, vs[:, :, None], fill)
                acc = masked.min(axis=1) if pop == "min" else masked.max(axis=1)
            else:
                raise ValueError(pop)
            partials[name] = acc.reshape(-1)
    return pkeys.reshape(-1), pcounts.reshape(-1), partials


def groupby_partition_hash(
    table: Table,
    *,
    key: str = "k",
    aggs: dict[str, str],
    num_groups: int,
    block: int = 256,
):
    """Two-phase aggregation: MXU one-hot tile partials + sorted combine.

    The tile plays the role of the GPU thread block's shared-memory hash
    table; the one-hot matmul is the scatter-free reduction (DESIGN.md §2).
    The combine phase runs over tile partials (<= distinct-per-tile of the
    input rows live), so for low-cardinality or skewed inputs the expensive
    pass shrinks by up to `block`x."""
    table = _nonempty(table, key)  # zero rows -> one all-sentinel row
    keys = table[key]
    # Build partial-op plan: ops needed per output agg (+ count for mean).
    cols_ops = {}
    for col, op in aggs.items():
        pop, _ = _PARTIAL[op]
        cols_ops[f"{col}_{op}"] = (table[col], pop)

    pkeys, pcounts, partials = _tile_partials(keys, cols_ops, block)

    # Phase 2: sorted combine over partials (sum of sums / min of mins / ...).
    with prim.phase("partition"):
        sk, scnt, *svals = prim.sort_pairs(pkeys, pcounts, *partials.values())
    with prim.phase("aggregate"):
        valid_row = sk != KEY_SENTINEL
        boundary = jnp.concatenate([jnp.ones((1,), bool), sk[1:] != sk[:-1]]) & valid_row
        gid = jnp.cumsum(boundary.astype(jnp.int32)) - 1
        n_found = gid[-1] + 1
        gid = jnp.where(valid_row & (gid < num_groups), gid, num_groups)

        out_keys = jnp.full((num_groups + 1,), KEY_SENTINEL, keys.dtype)
        out_keys = out_keys.at[gid].set(jnp.where(valid_row, sk, KEY_SENTINEL), mode="drop")
        counts = jax.ops.segment_sum(jnp.where(valid_row, scnt, 0.0), gid,
                                     num_segments=num_groups + 1)

        out = {key: out_keys[:num_groups]}
        for (name, (_, pop)), sv in zip(cols_ops.items(), svals):
            _, cop = _PARTIAL[{"sum": "sum", "count": "count", "min": "min", "max": "max"}[pop]]
            if cop == "sum":
                acc = jax.ops.segment_sum(jnp.where(valid_row, sv, 0.0), gid,
                                          num_segments=num_groups + 1)
            elif cop == "min":
                acc = jax.ops.segment_min(jnp.where(valid_row, sv, jnp.finfo(jnp.float32).max),
                                          gid, num_segments=num_groups + 1)
            else:
                acc = jax.ops.segment_max(jnp.where(valid_row, sv, jnp.finfo(jnp.float32).min),
                                          gid, num_segments=num_groups + 1)
            out[name] = acc[:num_groups]
        # finalize means / counts dtype
        for col, op in aggs.items():
            name = f"{col}_{op}"
            if op == "mean":
                out[name] = out[name] / jnp.maximum(counts[:num_groups], 1.0)
            if op == "count":
                out[name] = out[name].astype(jnp.int32)
    count = jnp.minimum(n_found, num_groups)
    return Table(out), count


# ---------------------------------------------------------------------------
# Partition-based (high group cardinality; paper's third algorithm)
# ---------------------------------------------------------------------------
# default padded-block capacity per partition (the BUILD_BLOCK analogue);
# a single key's rows co-hash no matter the fan-out, so per-key multiplicity
# beyond this cannot be partitioned away — the engine guard checks against it.
# The layout targets E[partition rows] <= row_block/2 (hashed keys at the
# low multiplicities the chooser routes here put the 2x-mean tail far below
# fp precision), so the padded slot space stays ~2-4x n instead of the 6x a
# quarter-full 256-row block cost — the slot space is what every blocked
# aggregation pass streams over.
PARTITION_ROW_BLOCK = 128


def choose_groupby_partition_bits(n_rows: int,
                                  row_block: int = PARTITION_ROW_BLOCK) -> int:
    """Fan-out so that E[partition rows] <= row_block/2: with hashed keys and
    per-key multiplicity << row_block (the high-cardinality regime this
    algorithm targets), overflow of the padded block becomes negligible.

    Capped at 16 bits (65536 partitions); past the cap the BLOCK must grow
    instead — `_partition_layout` below holds the invariant either way."""
    target = max(1, (2 * n_rows) // row_block)
    return max(1, min(16, (target - 1).bit_length()))


def _partition_layout(n_rows: int, row_block: int,
                      partition_bits: int | None) -> tuple[int, int]:
    """(p_bits, row_block) honoring the VMEM-fit invariant
    E[rows/partition] <= row_block/2. When the requested block would need
    more than the 16-bit fan-out cap, the block grows to cover the expected
    partition size — never silently over-fill partitions (that would drop
    every partition's overhang, not a tail). Explicit partition_bits skips
    the auto-grow: the caller owns the layout (the checked driver relies on
    this to pin its escalated geometry)."""
    if partition_bits is not None:
        return partition_bits, row_block
    p_bits = choose_groupby_partition_bits(n_rows, row_block)
    need = -(-2 * n_rows // (1 << p_bits))  # block for E[size] == block/2
    if need > row_block:
        row_block = 1 << int(need - 1).bit_length()
    return p_bits, row_block


def _partition_digits(keys: jax.Array, p_bits: int) -> jax.Array:
    """Hash-derived partition digit per row, in [0, P]: valid keys spread
    over [0, P) via the avalanching hash (a digit is a pure function of the
    key, so every group lands wholly in one partition); KEY_SENTINEL padding
    floods its own dedicated partition P, so a join output that is half
    padding can never crowd valid keys out of a shared bucket.

    Float keys are bitcast (not value-cast) so every distinct float hashes
    distinctly, with -0.0 normalized to +0.0 first — the two compare equal,
    so they must co-partition the way the sort path co-groups them. NaN keys
    are outside the key contract (valid keys are >= 0, table.py) and are
    routed to the padding partition, i.e. dropped like sentinels."""
    if jnp.issubdtype(keys.dtype, jnp.floating):
        sentinel = jnp.isnan(keys) | (keys == KEY_SENTINEL)
        normed = jnp.where(keys == 0.0, jnp.zeros((), keys.dtype), keys)
        hashable = jax.lax.bitcast_convert_type(
            normed, jnp.dtype(f"int{keys.dtype.itemsize * 8}"))
    else:
        hashable = keys
        sentinel = keys == jnp.asarray(KEY_SENTINEL, keys.dtype)
    d = (hash32(hashable) & ((1 << p_bits) - 1)).astype(jnp.int32)
    return jnp.where(sentinel, 1 << p_bits, d)


def groupby_partition(
    table: Table,
    *,
    key: str = "k",
    aggs: dict[str, str],
    num_groups: int,
    row_block: int = PARTITION_ROW_BLOCK,
    partition_bits: int | None = None,
):
    """Partition-based grouped aggregation (DESIGN.md §8).

    Multi-pass radix partition on the hashed group key's bits until each
    partition fits a VMEM-resident `row_block`-row block, then aggregate
    every partition independently with the block-local sort machinery of
    `partition_hash` — no global sort and no cross-partition combine pass,
    because a group lives in exactly one partition. Dense per-partition
    outputs are concatenated (stable compaction) into the shared
    (Table, valid_count) contract; output rows are ordered by
    (partition, key), not globally key-sorted.

    One-permutation materialization: the partition is planned once
    (`plan_partition_permutation`, sort-free by default — DESIGN.md §10) and
    each column — key and payloads — is gathered exactly once, straight into
    the blocked (P, row_block) layout.

    The per-partition aggregation is scatter-free: one stable block-local
    sort carries the key and every aggregate input together (VMEM-resident
    work — the shared-memory hash-table analogue), group sums fall out of
    masked cumulative sums differenced at run boundaries, and the dense
    output is compacted by a binary search over the monotone run ids — no
    segment scatter, no slot-space scatter, no compaction scatter (min/max
    aggregates alone still need one segmented reduction each).

    Static-shape caveat: a partition holding more than `row_block` rows has
    its overhang dropped. `choose_groupby_partition_bits` sizes the fan-out
    for E[rows/partition] <= row_block/2, which makes overflow negligible for
    the high-cardinality, low-multiplicity inputs the strategy chooser routes
    here; heavy per-key duplication co-hashes regardless of fan-out, so
    skewed/duplicated inputs belong to `partition_hash` instead. Use
    `groupby_partition_checked` for an eager overflow check + escalation."""
    table = _nonempty(table, key)  # zero rows -> one all-sentinel row
    keys = table[key]
    n = keys.shape[0]
    p_bits, row_block = _partition_layout(n, row_block, partition_bits)
    P = 1 << p_bits
    with prim.phase("partition"):
        digits = _partition_digits(keys, p_bits)
        # One-permutation plan over P+1 partitions (the extra one swallows
        # sentinel padding and is never materialized). The key column comes back
        # already partitioned (Algorithm 1's key-rides-along idiom).
        perm, (keys_part,), offsets, sizes = prim.plan_partition_permutation(
            digits, P + 1, carry=(keys,))

        # Blocked VMEM layout of the P valid partitions: position (p, i) holds
        # the i-th row of partition p. Composing the block map with the planned
        # permutation gathers every payload column from the ORIGINAL table
        # exactly once; the key is a clustered read of the carried column.
        i = jnp.arange(row_block, dtype=jnp.int32)[None, :]
        pos = offsets[:P, None] + i
        in_part = i < jnp.minimum(sizes[:P, None], row_block)
        pos_c = jnp.clip(pos, 0, n - 1)
        src = jnp.take(perm, pos_c)  # (P, row_block) source rows for payloads
        kblocks = jnp.where(in_part, jnp.take(keys_part, pos_c),
                            jnp.asarray(KEY_SENTINEL, keys.dtype))

    val_names = [c for c, op in aggs.items() if op != "count"]
    uniq_cols = list(dict.fromkeys(val_names))
    with prim.phase("materialize"):
        vblocks = [jnp.take(table[c], src) for c in uniq_cols]  # col's ONE gather
    # Per-partition grouping: ONE stable block-local sort moves the key and
    # every aggregate input together (a group lives in exactly one
    # partition, so block runs are final groups). Sentinel slots sort to the
    # front of their block and are masked out of every reduction.
    with prim.phase("partition"):
        sorted_ = jax.lax.sort((kblocks,) + tuple(vblocks), num_keys=1,
                               is_stable=True)
    with prim.phase("aggregate"):
        ks = sorted_[0]
        vsorted = dict(zip(uniq_cols, sorted_[1:]))
        n_slots = P * row_block
        ksf = ks.reshape(-1)
        valid = (ksf != jnp.asarray(KEY_SENTINEL, keys.dtype))
        head = jnp.concatenate(
            [jnp.ones((P, 1), bool), ks[:, 1:] != ks[:, :-1]], axis=1).reshape(-1)
        bnd = head & valid
        rid = jnp.cumsum(bnd.astype(jnp.int32)) - 1  # monotone run id per slot
        n_found = rid[-1] + 1 if n_slots else jnp.zeros((), jnp.int32)
        count = jnp.minimum(n_found, num_groups)

        # Dense compaction without a scatter: rid is sorted, so the r-th run's
        # first slot is a binary search; run r spans [starts[r], starts[r+1]).
        r_iota = jnp.arange(num_groups + 1, dtype=jnp.int32)
        starts = jnp.searchsorted(rid, r_iota, side="left").astype(jnp.int32)
        starts_c = jnp.clip(starts[:num_groups], 0, max(n_slots - 1, 0))
        present = jnp.arange(num_groups, dtype=jnp.int32) < count
        out_keys = jnp.where(present, jnp.take(ksf, starts_c),
                             jnp.asarray(KEY_SENTINEL, keys.dtype))

        def run_total(per_slot):
            """Count over each run via an exclusive cumsum differenced at run
            boundaries — int32 is exact however long the prefix, never a
            scatter."""
            ecs = jnp.concatenate([jnp.zeros((1,), per_slot.dtype),
                                   jnp.cumsum(per_slot)])
            return jnp.take(ecs, starts[1:]) - jnp.take(ecs, starts[:num_groups])

        # Float run sums use BLOCK-LOCAL exclusive cumsums instead: a run never
        # spans blocks (valid rows are a block's sorted suffix), so the prefix a
        # difference cancels is bounded by one block's magnitude — the rounding
        # error of a global n-slot prefix would grow with the whole relation.
        s_flat = starts[:num_groups]
        e_flat = starts[1:]
        row_s = jnp.minimum(s_flat // row_block, P - 1)
        col_s = s_flat - (s_flat // row_block) * row_block
        col_e = jnp.where(e_flat // row_block == s_flat // row_block,
                          e_flat - (e_flat // row_block) * row_block, row_block)

        def run_block_total(masked2d):
            ecs = jnp.concatenate(
                [jnp.zeros((P, 1), masked2d.dtype), jnp.cumsum(masked2d, axis=1)],
                axis=1).reshape(-1)  # (P * (row_block+1),)
            hi = jnp.take(ecs, row_s * (row_block + 1) + col_e)
            lo = jnp.take(ecs, row_s * (row_block + 1) + col_s)
            return jnp.where(present, hi - lo, jnp.zeros((), masked2d.dtype))

        valid2d = valid.reshape(P, row_block)
        counts = run_total(valid.astype(jnp.int32))
        cols = {key: out_keys}
        for col, op in aggs.items():
            if op == "count":
                cols[f"{col}_{op}"] = counts
                continue
            vs = vsorted[col].reshape(-1)
            if op in ("sum", "mean"):
                acc = run_block_total(
                    jnp.where(valid2d, vsorted[col], jnp.zeros((), vs.dtype)))
            else:  # min/max: not expressible as a cumsum difference
                seg = jnp.where(valid & (rid < num_groups), rid, num_groups)
                fill = (jnp.finfo if jnp.issubdtype(vs.dtype, jnp.floating)
                        else jnp.iinfo)(vs.dtype)
                masked = jnp.where(valid, vs, fill.max if op == "min" else fill.min)
                acc = _seg_reduce(op, masked, seg, num_groups + 1)[:num_groups]
            cols[f"{col}_{op}"] = _finalize(op, acc, counts)
    return Table(cols), count


def groupby_partition_overflowed(
    keys: jax.Array, *, row_block: int = PARTITION_ROW_BLOCK,
    partition_bits: int | None = None
):
    """Host-side check: would any valid partition exceed the (layout-
    adjusted) block? Returns (overflowed, p_bits, max_partition_rows).
    Sentinel rows are excluded — their dedicated partition is allowed to
    overflow."""
    p_bits, row_block = _partition_layout(keys.shape[0], row_block,
                                          partition_bits)
    digits = _partition_digits(keys, p_bits)
    sizes = jnp.bincount(digits, length=(1 << p_bits) + 1)[:-1]
    mx = int(jnp.max(sizes))
    return mx > row_block, p_bits, mx


def groupby_partition_checked(
    table: Table,
    *,
    key: str = "k",
    aggs: dict[str, str],
    num_groups: int,
    row_block: int = PARTITION_ROW_BLOCK,
    max_extra_bits: int = 4,
    max_attempts: int = 8,
    with_report: bool = False,
    **kw,
):
    """groupby_partition on the resilience ladder (DESIGN.md §13): first
    add fan-out bits — separating co-hashed distinct groups — then, if a
    single key's duplication still overflows (more bits cannot split one
    key), revert the extra bits and grow the block to cover the base
    layout's observed maximum (always the smaller geometry: splitting can
    at best divide the max by the same 2^extra it multiplies the partition
    count by); as a last rung, fall back to the always-exact sort
    strategy. Each check is a cheap host-side histogram; exhaustion raises
    `EscalationExhausted` instead of dropping partition overhang.

    `with_report=True` additionally returns the `EscalationReport`."""
    from repro.resilience import EscalationStep, Ladder

    table = _nonempty(table, key)
    keys = table[key]
    # resolve the auto layout ONCE, then pin it explicitly through the
    # escalation (explicit partition_bits disables the auto-grow)
    base_bits, base_block = _partition_layout(
        keys.shape[0], row_block, kw.pop("partition_bits", None))
    knobs = {"strategy": "partition", "partition_bits": base_bits,
             "row_block": base_block}
    base_mx: dict = {}  # heaviest base-layout partition, cached by check()

    def check(kn):
        if kn["strategy"] != "partition":
            return True, "sort fallback (always exact)", None
        over, _, mx = groupby_partition_overflowed(
            keys, row_block=kn["row_block"],
            partition_bits=kn["partition_bits"])
        if kn["partition_bits"] == base_bits:
            base_mx.setdefault("mx", mx)
        return (not over,
                f"partition rows {mx} > block {kn['row_block']}" if over
                else "", mx)

    def grow_bits(kn, diag):
        if kn["strategy"] != "partition" or kn["partition_bits"] >= 20:
            return None
        return {**kn, "partition_bits": kn["partition_bits"] + 1}

    def grow_block(kn, diag):
        if kn["strategy"] != "partition":
            return None
        mx0 = max(base_mx.get("mx", 0), 1)
        rb = 1 << max(int(mx0 - 1).bit_length(),
                      int(base_block - 1).bit_length())
        if rb <= kn["row_block"] and kn["partition_bits"] == base_bits:
            rb = kn["row_block"] * 2  # forced overflow: grow anyway
        return {**kn, "partition_bits": base_bits, "row_block": rb}

    def to_sort(kn, diag):
        return {**kn, "strategy": "sort"}

    ladder = Ladder("groupby_partition", [
        EscalationStep("partition_bits", grow_bits, max_times=max_extra_bits),
        EscalationStep("row_block", grow_block, max_times=1),
        EscalationStep("strategy:sort", to_sort, max_times=1),
    ], max_attempts=max_attempts)
    report = ladder.resolve(knobs, check)
    kn = report.final_knobs
    if kn["strategy"] == "sort":
        out = groupby_sort(table, key=key, aggs=aggs, num_groups=num_groups)
    else:
        out = groupby_partition(
            table, key=key, aggs=aggs, num_groups=num_groups,
            row_block=kn["row_block"], partition_bits=kn["partition_bits"],
            **kw)
    return (out, report) if with_report else out


# ---------------------------------------------------------------------------
# Scatter baseline (dense key domain)
# ---------------------------------------------------------------------------
def groupby_scatter(
    table: Table,
    *,
    key: str = "k",
    aggs: dict[str, str],
    num_groups: int,
):
    """Direct scatter aggregation for keys in [0, num_groups) — the
    atomicAdd analogue. Unclustered writes; viable only when the accumulator
    array stays cache/VMEM-resident. Out-of-domain keys (including
    KEY_SENTINEL padding) are dropped, and — like the other strategies —
    the output is compacted to a dense prefix (present groups in ascending
    key order, rows >= valid_count are padding), so all strategies share
    one (Table, valid_count) contract."""
    table = _nonempty(table, key)  # zero rows -> one all-sentinel row
    keys = table[key]
    if not jnp.issubdtype(keys.dtype, jnp.integer):
        raise TypeError(
            f"scatter group-by needs integer keys, got {keys.dtype}; "
            "float keys would be silently floored into merged groups")
    with prim.phase("aggregate"):
        in_domain = (keys >= 0) & (keys < num_groups)
        gid = jnp.where(in_domain, keys, num_groups).astype(jnp.int32)
        counts = jax.ops.segment_sum(
            in_domain.astype(jnp.int32), gid, num_segments=num_groups + 1
        )[:num_groups]
        present = counts > 0
        out = {key: jnp.arange(num_groups, dtype=keys.dtype)}
        for col, op in aggs.items():
            vals = table[col]
            if op in ("sum", "mean"):
                vals = jnp.where(in_domain, vals, 0)
            acc = _seg_reduce(op, vals, gid, num_groups + 1)[:num_groups]
            out[f"{col}_{op}"] = _finalize(op, acc, counts)
        names = list(out)
        compacted, n_present = prim.compact(present, [out[n] for n in names],
                                            num_groups)
        out = dict(zip(names, compacted))
        out[key] = jnp.where(jnp.arange(num_groups) < n_present, out[key],
                             jnp.asarray(KEY_SENTINEL, keys.dtype))
    return Table(out), n_present


def groupby_sort_pallas(
    table: Table,
    *,
    key: str = "k",
    aggs: dict[str, str],
    num_groups: int,
    tile: int = 256,
):
    """Sort-based group-by whose per-tile partial reduction runs in the
    Pallas segsum kernel (scatter-free MXU path; interpret-mode on CPU).
    Sum/count/mean only (kernel computes sums+counts).

    The key sort is planned once (one-permutation layer) and each payload
    column costs one gather + one kernel pass. The count kernel is key-only
    and identical for every column, so it runs at most once — and only when
    a mean/count aggregate actually needs it."""
    from repro.kernels import ops as kops

    table = _nonempty(table, key)  # zero rows -> one all-sentinel row
    keys = table[key]
    for op in aggs.values():
        if op not in ("sum", "mean", "count"):
            raise ValueError(f"sort_pallas supports sum/mean/count, got {op}")
    with prim.phase("partition"):
        sk, perm = prim.plan_sort_permutation(keys)
    out = {}
    count = gc = None
    if any(op in ("mean", "count") for op in aggs.values()):
        # hoisted key-only count pass (shared by every mean/count column)
        with prim.phase("aggregate"):
            out[key], gc, count = kops.groupby_sorted_sum(
                sk, jnp.ones(sk.shape, jnp.float32), num_groups, "pallas",
                tile=tile)
    for col, op in aggs.items():
        if op == "count":
            out[f"{col}_{op}"] = gc.astype(jnp.int32)
            continue
        with prim.phase("materialize"):
            sv = prim.apply_permutation(perm, table[col])  # one gather per column
        with prim.phase("aggregate"):
            gk, gs, cnt = kops.groupby_sorted_sum(sk, sv.astype(jnp.float32),
                                                  num_groups, "pallas", tile=tile)
            if count is None:
                out[key], count = gk, cnt
            out[f"{col}_{op}"] = gs if op == "sum" else gs / jnp.maximum(gc, 1.0)
    return Table(out), count


def choose_groupby_strategy(
    n_rows: int,
    est_groups: float,
    *,
    key_min: float | None = None,
    key_max: float | None = None,
    zipf: float = 0.0,
    dense_domain_limit: int = 1 << 18,
    integer_key: bool = True,
) -> tuple[str, str]:
    """Cardinality-based strategy heuristic, mirroring the paper's
    hash/sort/partition guidance for grouped aggregation (and Fig. 18's
    structure: pick the cheapest access pattern the distribution allows).

    Returns (strategy, rationale):
      * dense, small key domains -> 'scatter' (the accumulator array stays
        cache/VMEM-resident, so the unclustered writes are cheap — the
        atomicAdd-on-shared-memory regime);
      * heavy duplication (rows >> groups) or skew -> 'partition_hash'
        (tile-local pre-aggregation collapses duplicates before the
        expensive pass, the shared-memory-hash-table regime);
      * high cardinality + hashable (integer) keys -> 'partition' (the
        paper's partition-based algorithm: radix-partition on hashed key
        bits until each partition's group set fits a VMEM-resident block,
        aggregate partitions independently — the pass count scales with
        log(groups) instead of the key width, and there is no global
        sort or combine; requires low per-key multiplicity, which high
        cardinality implies);
      * high cardinality, non-integer keys -> 'sort' (one sequential sort
        pass beats hash tables that spill out of fast memory — the GFTR
        insight; float keys cannot be radix-bucketed by value-hash without
        a bitcast normalization, so sort stays the robust fallback).
    """
    domain = None
    # scatter indexes the accumulator by key value, so the keys must be
    # non-negative integers in a small domain
    if (integer_key and key_min is not None and key_max is not None
            and key_min >= 0):
        domain = int(key_max) + 1
    if domain is not None and domain <= dense_domain_limit and domain <= max(
        4 * est_groups, 1024
    ):
        return "scatter", (
            f"dense key domain [0, {domain}) fits a resident accumulator"
        )
    if zipf > 1.0:
        return "partition_hash", (
            f"skewed keys (zipf~{zipf:.2f}): tile pre-aggregation absorbs "
            "heavy hitters"
        )
    if est_groups * 8 <= n_rows:
        return "partition_hash", (
            f"rows/groups ~ {n_rows / max(est_groups, 1.0):.0f}x: tile "
            "pre-aggregation shrinks the combine pass"
        )
    if integer_key:
        return "partition", (
            f"high cardinality (~{est_groups:.0f} groups, low multiplicity): "
            "radix-partition to VMEM-resident accumulators, no global "
            "sort/combine"
        )
    return "sort", (
        "high cardinality, non-integer keys: sequential sort pass beats "
        "spilling hash tables"
    )


def group_aggregate(
    table: Table,
    *,
    key: str = "k",
    aggs: dict[str, str],
    num_groups: int,
    strategy: str = "sort",
    **kw,
):
    """Unified entry point.
    strategy in {'sort', 'partition', 'partition_hash', 'scatter',
    'sort_pallas'}."""
    fn = {
        "sort": groupby_sort,
        "partition": groupby_partition,
        "partition_hash": groupby_partition_hash,
        "scatter": groupby_scatter,
        "sort_pallas": groupby_sort_pallas,
    }[strategy]
    return fn(table, key=key, aggs=aggs, num_groups=num_groups, **kw)
