"""Partitioned hash join: PHJ-UM (GFUR, §3.2) and PHJ-OM (GFTR, §4.3).

The paper's PHJ-OM redesign replaces bucket-chaining (non-deterministic,
fragmented) with stable RADIX-PARTITION into contiguous arrays + histogram/
prefix-sum offsets. Our TPU port is deterministic by construction
(prefix-sum ranks, no atomics — DESIGN.md §2), so the GFTR requirement
"partitioning (key, col_1) gives the same layout as (key, col_2)" holds
exactly.

Match finding mirrors the paper's co-partition scheme: the build-side
partition plays the role of the shared-memory hash table (here: a fixed-
capacity VMEM-resident block), and probe keys stream against it. The paper
itself describes the multi-bucket case as "resembling a block nested loop
join"; on TPU the probe is a vectorized equality over the block — the
hash_probe Pallas kernel implements the same loop with explicit VMEM tiling.

Static-shape notes: build partitions are padded to `build_block` capacity
(contiguous + constant-time indexable — the paper's de-fragmentation
requirement); an overflow diagnostic is returned so callers can re-run with
more partition bits. Probe-side partitions are never padded: probe rows are
processed in partitioned order (this is also the paper's probe-side
sub-partitioning load-balance trick, for free).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import primitives as prim
from .table import KEY_SENTINEL, Table


def hash32(x: jax.Array) -> jax.Array:
    """Murmur3-style finalizer; avalanches all input bits into 32."""
    if x.dtype.itemsize > 4:
        x = (x ^ (x >> 32)).astype(jnp.uint32)
    else:
        x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x


# default padded-block capacity per build partition; rows of ONE key beyond
# this cannot be separated by more partition bits (duplicates co-hash), so
# m:n joins with heavier per-key multiplicity must use sort-merge instead
BUILD_BLOCK = 256


def choose_partition_bits(n_build: int, build_block: int) -> int:
    """Fan-out so that E[partition size] <= build_block/4 (overflow of the
    padded block becomes negligible for hashed keys)."""
    target = max(1, (4 * n_build) // build_block)
    return max(1, min(20, (target - 1).bit_length()))


def _digits(keys, p_bits, hash_keys):
    """Partition digit per row, in [0, p_bits^2]: valid keys spread over
    [0, P) by the hash; KEY_SENTINEL rows (masked padding from an upstream
    operator) flood their own dedicated partition P so they can never crowd
    valid keys out of a shared build block — without this, a join input
    that is half padding concentrates every sentinel in one hash bucket and
    evicts the valid keys that co-hash there (silent dropped matches)."""
    h = hash32(keys) if hash_keys else keys.astype(jnp.uint32)
    d = (h & ((1 << p_bits) - 1)).astype(jnp.int32)
    sentinel = keys == jnp.asarray(KEY_SENTINEL, keys.dtype)
    return jnp.where(sentinel, 1 << p_bits, d)


def _nonempty(table: Table, key: str) -> Table:
    """A zero-row relation breaks the static-shape plumbing (empty
    bincounts, (0,)-vs-(1,) boundary concats). Substitute ONE all-sentinel
    row: the sentinel key is dropped by every probe/build/aggregate by
    construction, so results are identical to the true empty input while
    every intermediate keeps a non-degenerate shape."""
    if table.num_rows:
        return table
    cols = {}
    for n in table.column_names:
        c = table[n]
        fill = KEY_SENTINEL if n == key else 0
        cols[n] = jnp.full((1,), fill, c.dtype)
    return Table(cols)


def _chunked(f, arr_len, chunk, *arrays):
    """Apply f to row-chunks of the arrays sequentially (bounded memory),
    concatenating results. Pads to a chunk multiple."""
    n_pad = -arr_len % chunk
    padded = [jnp.pad(a, [(0, n_pad)] + [(0, 0)] * (a.ndim - 1)) for a in arrays]
    stacked = [a.reshape((-1, chunk) + a.shape[1:]) for a in padded]
    outs = jax.lax.map(lambda xs: f(*xs), tuple(stacked))
    outs = jax.tree_util.tree_map(lambda o: o.reshape((-1,) + o.shape[2:])[:arr_len], outs)
    return outs


# ---------------------------------------------------------------------------
# Build-side padded blocks
# ---------------------------------------------------------------------------
def blocked_partitions(arr_part: jax.Array, off: jax.Array, sz: jax.Array,
                       cap: int, fill):
    """Pad each contiguous partition of a partitioned column to `cap` rows:
    (P, cap) blocks where slot (p, i) holds the i-th row of partition p and
    out-of-partition slots carry `fill`. The single home of the padding
    geometry — key blocks, virtual-ID blocks, and the group-join's value
    blocks must all agree on it."""
    i = jnp.arange(cap, dtype=jnp.int32)[None, :]
    idx = off[:, None].astype(jnp.int32) + i
    valid = i < sz[:, None]
    idx_c = jnp.clip(idx, 0, arr_part.shape[0] - 1)
    return jnp.where(valid, jnp.take(arr_part, idx_c), fill), idx, valid


def build_blocks(keys_part: jax.Array, off: jax.Array, sz: jax.Array, cap: int):
    """Pad each contiguous partition to `cap` rows -> (P, cap) key blocks and
    (P, cap) virtual-ID blocks (positions in the partitioned array).
    Returns (bkeys, bvids, overflow)."""
    bkeys, idx, valid = blocked_partitions(keys_part, off, sz, cap, KEY_SENTINEL)
    bvids = jnp.where(valid, idx, -1)
    overflow = jnp.max(sz) > cap
    return bkeys, bvids, overflow


# ---------------------------------------------------------------------------
# Match finding
# ---------------------------------------------------------------------------
def probe_pk_fk(bkeys, off_r, probe_keys, probe_digits, chunk=8192):
    """For each probe row: find its (unique) match in the build block of its
    co-partition. Returns (vid_r, matched), both clustered in probe order."""

    def body(pk, pd):
        # sentinel rows carry digit P (their dedicated partition, which has
        # no build block); clip to a real block — the pk != KEY_SENTINEL
        # guard already makes every comparison for them False
        pd = jnp.minimum(pd, bkeys.shape[0] - 1)
        cand = jnp.take(bkeys, pd, axis=0)  # (chunk, capR)
        eq = (cand == pk[:, None]) & (pk[:, None] != KEY_SENTINEL)
        hit = jnp.argmax(eq, axis=1).astype(jnp.int32)
        matched = jnp.any(eq, axis=1)
        vid_r = jnp.take(off_r, jnp.minimum(pd, off_r.shape[0] - 1)
                         ).astype(jnp.int32) + hit
        return vid_r, matched

    return _chunked(body, probe_keys.shape[0], chunk, probe_keys, probe_digits)


def probe_counts(bkeys, probe_keys, probe_digits, chunk=8192):
    """m:n: number of build matches per probe row."""

    def body(pk, pd):
        pd = jnp.minimum(pd, bkeys.shape[0] - 1)  # sentinel digit P -> any block
        cand = jnp.take(bkeys, pd, axis=0)
        eq = (cand == pk[:, None]) & (pk[:, None] != KEY_SENTINEL)
        return jnp.sum(eq, axis=1).astype(jnp.int32)

    return _chunked(body, probe_keys.shape[0], chunk, probe_keys, probe_digits)


def probe_kth_match(bkeys, off_r, probe_keys, probe_digits, rows, ranks, chunk=8192):
    """m:n expansion: for output row t assigned to probe row `rows[t]`, find
    its `ranks[t]`-th match in the co-partition block."""

    def body(row, rank):
        pk = jnp.take(probe_keys, row)
        pd = jnp.minimum(jnp.take(probe_digits, row), bkeys.shape[0] - 1)
        cand = jnp.take(bkeys, pd, axis=0)
        eq = (cand == pk[:, None]) & (pk[:, None] != KEY_SENTINEL)
        csum = jnp.cumsum(eq.astype(jnp.int32), axis=1)
        # k-th set bit = first position where csum > k
        pos = jnp.sum((csum <= rank[:, None]).astype(jnp.int32), axis=1)
        pos = jnp.minimum(pos, cand.shape[1] - 1)
        return jnp.take(off_r, pd).astype(jnp.int32) + pos

    return _chunked(body, rows.shape[0], chunk, rows, ranks)


# ---------------------------------------------------------------------------
# Join driver
# ---------------------------------------------------------------------------
def phj_join(
    R: Table,
    S: Table,
    *,
    key: str = "k",
    pattern: str = "gftr",  # "gftr" (PHJ-OM) | "gfur" (PHJ-UM)
    out_size: int | None = None,
    mode: str = "pk_fk",
    build_block: int = BUILD_BLOCK,
    partition_bits: int | None = None,
    hash_keys: bool = True,
    probe_chunk: int = 8192,
    probe_impl: str = "xla",  # "xla" | "pallas" (co-partition probe kernel)
):
    """End-to-end partitioned hash join. Returns (Table, valid_count).

    Build partitions are padded to `build_block`; if any partition would
    overflow (duplicate-heavy build keys), `phj_join_checked` re-runs with
    more partition bits (the paper's multi-pass fan-out escalation).
    """
    if out_size is None:
        out_size = S.num_rows if mode == "pk_fk" else S.num_rows * 2
    out_size = max(out_size, 1)
    R = _nonempty(R, key)
    S = _nonempty(S, key)
    r_pay = [n for n in R.column_names if n != key]
    s_pay = [n for n in S.column_names if n != key]
    p_bits = (
        partition_bits
        if partition_bits is not None
        else choose_partition_bits(R.num_rows, build_block)
    )
    P = 1 << p_bits

    with prim.phase("partition"):
        dig_r = _digits(R[key], p_bits, hash_keys)
        dig_s = _digits(S[key], p_bits, hash_keys)
        # One-permutation transform plan (multi-pass radix semantics;
        # determinism by construction — §4.3's requirement): the partition
        # is planned once per side and every column it touches costs exactly
        # one gather. P + 1 partitions: the extra one swallows sentinel rows
        # (see _digits) and never gets a build block or a probe pass.
        perm_r, off_r, sz_r = prim.plan_partition_permutation(dig_r, P + 1)
        perm_s, off_s, sz_s = prim.plan_partition_permutation(dig_s, P + 1)

        kr = prim.apply_permutation(perm_r, R[key])
        ks, dig_s_part = prim.apply_permutation(perm_s, S[key], dig_s)

        bkeys, _, overflow = build_blocks(kr, off_r[:P], sz_r[:P], build_block)

    with prim.phase("probe"):
        if mode == "pk_fk":
            if probe_impl == "pallas":
                from repro.kernels import ops as _kops

                vid_r, matched = _kops.hash_probe(bkeys, off_r[:P], ks,
                                                  off_s[:P], sz_s[:P], "pallas")
            else:
                vid_r, matched = probe_pk_fk(bkeys, off_r, ks, dig_s_part,
                                             probe_chunk)
            vid_s = jnp.arange(ks.shape[0], dtype=jnp.int32)
            (keys_o, vr, vs), count = prim.compact(
                matched, [ks, vid_r, vid_s], out_size, fill=KEY_SENTINEL
            )
            valid = jnp.arange(out_size) < count
        else:
            counts = probe_counts(bkeys, ks, dig_s_part, probe_chunk)
            rows, ranks, valid, total = prim.expand_offsets(counts, out_size)
            vr = probe_kth_match(bkeys, off_r, ks, dig_s_part, rows, ranks,
                                 probe_chunk)
            vs = rows
            keys_o = jnp.where(valid, jnp.take(ks, vs), KEY_SENTINEL)
            count = jnp.minimum(total, out_size)

    with prim.phase("materialize"):
        ID_R = jnp.where(valid, vr, -1)
        ID_S = jnp.where(valid, vs, -1)

        cols = {key: keys_o}
        if pattern == "gfur":
            # UM: translate to physical IDs of the untransformed inputs.
            pid_r = jnp.where(valid, jnp.take(perm_r, jnp.clip(vr, 0, R.num_rows - 1)), -1)
            pid_s = jnp.where(valid, jnp.take(perm_s, jnp.clip(vs, 0, S.num_rows - 1)), -1)
            for n in r_pay:
                cols[n] = prim.gather(R[n], pid_r, fill=0)  # unclustered
            for n in s_pay:
                cols[n] = prim.gather(S[n], pid_s, fill=0)  # unclustered
        elif pattern == "gftr":
            # OM: gather from partitioned relations. Probe-side IDs are
            # perfectly clustered; build-side IDs are clustered within
            # partitions (§4.3).
            # One column at a time (Algorithm 1's lazy transform): each
            # column's transform waits for the previous column's gather,
            # so a single transformed column is live at once.
            prev = ID_R
            for T, perm, ids, names in ((R, perm_r, ID_R, r_pay),
                                        (S, perm_s, ID_S, s_pay)):
                for n in names:
                    col, _ = jax.lax.optimization_barrier((T[n], prev))
                    tr_n = prim.apply_permutation(perm, col)  # col n's ONE gather
                    cols[n] = prev = prim.clustered_gather(tr_n, ids)
        else:
            raise ValueError(f"unknown pattern {pattern!r}")

    return Table(cols), count


def phj_overflowed(R: Table, *, key: str = "k", build_block: int = 256,
                   partition_bits: int | None = None, hash_keys: bool = True):
    """Host-side check: would any build partition exceed the padded block?"""
    p_bits = (partition_bits if partition_bits is not None
              else choose_partition_bits(R.num_rows, build_block))
    dig = _digits(R[key], p_bits, hash_keys)
    # the sentinel partition P is allowed to overflow (it never gets a block)
    sizes = jnp.bincount(dig, length=(1 << p_bits) + 1)[:-1]
    return bool(jnp.max(sizes) > build_block), p_bits


def escalate_partition_bits(R: Table, *, key: str = "k",
                            build_block: int = 256,
                            partition_bits: int | None = None,
                            hash_keys: bool = True,
                            max_extra_bits: int = 4) -> int:
    """Resolved fan-out after the checked drivers' escalation policy: add
    partition bits while any build co-partition would overflow its padded
    block (separating co-hashed distinct keys — the paper's multi-pass
    policy). Deterministic: each check is a cheap histogram, each retry
    uses strictly more bits. Shared by `phj_join_checked` and
    `groupjoin_checked`."""
    overflow, p_bits = phj_overflowed(R, key=key, build_block=build_block,
                                      partition_bits=partition_bits,
                                      hash_keys=hash_keys)
    extra = 0
    while overflow and extra < max_extra_bits:
        extra += 1
        overflow, _ = phj_overflowed(R, key=key, build_block=build_block,
                                     partition_bits=p_bits + extra,
                                     hash_keys=hash_keys)
    if extra:
        from repro.obs import metrics  # deferred: core never needs obs otherwise

        metrics.counter("core.overflow_escalations").inc()
    return p_bits + extra


def phj_join_checked(R: Table, S: Table, *, key: str = "k", max_extra_bits: int = 4,
                     build_block: int = 256, max_attempts: int = 8,
                     with_report: bool = False, **kw):
    """phj_join on the resilience ladder (DESIGN.md §13): add partition
    bits while any build co-partition would overflow its padded block (the
    paper's multi-pass fan-out escalation); when more bits cannot help —
    one key's duplicates co-hash no matter the fan-out — fall back to
    sort-merge, which is exact for any multiplicity. The old loop returned
    escalated-but-still-overflowing bits and silently dropped matches;
    the ladder either converges or raises `EscalationExhausted`.

    `with_report=True` additionally returns the `EscalationReport`."""
    from repro.resilience import EscalationStep, Ladder

    hash_keys = kw.get("hash_keys", True)
    base_bits = kw.pop("partition_bits", None)
    if base_bits is None:
        base_bits = choose_partition_bits(R.num_rows, build_block)
    knobs = {"algorithm": "phj", "partition_bits": base_bits,
             "build_block": build_block}

    def check(kn):
        if kn["algorithm"] != "phj":
            return True, "smj fallback (exact for any multiplicity)", None
        over, _ = phj_overflowed(R, key=key, build_block=kn["build_block"],
                                 partition_bits=kn["partition_bits"],
                                 hash_keys=hash_keys)
        return (not over,
                f"build partition > {kn['build_block']} rows" if over else "",
                None)

    def grow_bits(kn, diag):
        if kn["algorithm"] != "phj" or kn["partition_bits"] >= 20:
            return None
        return {**kn, "partition_bits": kn["partition_bits"] + 1}

    def to_smj(kn, diag):
        return {**kn, "algorithm": "smj"}

    ladder = Ladder("phj", [
        EscalationStep("partition_bits", grow_bits, max_times=max_extra_bits),
        EscalationStep("strategy:smj", to_smj, max_times=1),
    ], max_attempts=max_attempts)
    report = ladder.resolve(knobs, check)
    kn = report.final_knobs
    if kn["algorithm"] == "smj":
        from .sort_merge import smj_join  # deferred: no import cycle

        smj_kw = {k: v for k, v in kw.items()
                  if k in ("pattern", "out_size", "mode", "find_impl")}
        out = smj_join(R, S, key=key, **smj_kw)
    else:
        out = phj_join(R, S, key=key, build_block=kn["build_block"],
                       partition_bits=kn["partition_bits"], **kw)
    return (out, report) if with_report else out
