"""GPU-primitive analogues on TPU/XLA (paper §2.3).

The paper builds its joins from three vendor primitives:

  SORT-PAIRS(kin, vin, ...)      -> CUB LSD radix sort (8 bits / pass)
  RADIX-PARTITION(kin, vin, i, j)-> stable partition on radix bits [i, j)
  GATHER(in, map, out)           -> out[i] = in[map[i]]

TPU adaptation (DESIGN.md §2, §10): the *stability/determinism* requirement
that the paper had to engineer around CUDA atomics comes for free here — the
partition permutation is derived from prefix-sum ranks (production) or a
stable sort (reference arm), never from write races. `sort_pairs` uses XLA's
tuned TPU sort in the production path; partition plans default to the
kernel-backed histogram/prefix/rank pipeline (`kernels.ops.partition_plan`),
which is linear per pass and emits zero sort primitives;
`radix_sort_pairs` reproduces the paper's LSD pass structure exactly (one
stable partition per 8-bit digit) and is what the cost model counts.

All primitives are shape-polymorphic pure functions safe under jit/vmap.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

RADIX_BITS_PER_PASS = 8  # paper §2.3: Ampere RADIX-PARTITION does max 8 bits

# The phases of an operator's device work (the paper's question: how much of
# a join is partitioning, match finding and materialization). Every piece of
# work inside a core operator sits in exactly one of them:
#   partition    key-side transforms: digits, histograms, ranks, the
#                permutation, key blocks and sorts of keys (a sort that
#                carries payloads along with the keys counts here)
#   probe        match finding: hash probe, merge join, match counts and the
#                compaction of the match list
#   materialize  every move of a non-key payload column: its trip through a
#                partition permutation (GFTR's transform) and its gather into
#                the output (GFUR) — the paper's random-access cost
#   aggregate    reduction arithmetic: run boundaries, segmented sums, block
#                partials and combines
PHASES = ("partition", "probe", "materialize", "aggregate")


def phase(name: str):
    """`jax.named_scope` of one phase (`PHASES`): it names the compiled
    operations' `op_name` metadata, so a device trace can be split by phase.
    Costs nothing at run time."""
    if name not in PHASES:
        raise ValueError(f"unknown phase {name!r}; expected one of {PHASES}")
    return jax.named_scope(name)

# Production arm for full key-sort plans. XLA's tuned sort is the deliberate
# default (the paper's vendor SORT-PAIRS choice); 'radix' runs the same
# kernel-backed rank passes the partition planner uses, making SMJ's GFTR
# transform sort-free as well.
DEFAULT_SORT_PLAN_IMPL = "xla"


# ---------------------------------------------------------------------------
# SORT-PAIRS
# ---------------------------------------------------------------------------
def sort_pairs(keys: jax.Array, *values: jax.Array):
    """Stable key-value sort (CUB SORT-PAIRS analogue) via XLA's native sort.

    Returns (sorted_keys, *values_permuted_alike).
    """
    res = jax.lax.sort((keys,) + tuple(values), num_keys=1, is_stable=True)
    return res if values else res[0]


def argsort_stable(keys: jax.Array) -> jax.Array:
    """Stable argsort; out[i] = index of i-th smallest key."""
    iota = jnp.arange(keys.shape[0], dtype=jnp.int32)
    _, perm = jax.lax.sort((keys, iota), num_keys=1, is_stable=True)
    return perm


# ---------------------------------------------------------------------------
# One-permutation materialization layer (DESIGN.md §8)
#
# Algorithm 1's "transform lazily" only pays off if the transform itself is
# cheap: re-running the full sort/partition once per payload column turns one
# data-movement plan into O(C) of them. These planners run the sort/partition
# machinery ONCE, carrying only (key-or-digit, iota), and return a composed
# permutation; `apply_permutation` then materializes any number of payload
# columns at exactly one gather each.
# ---------------------------------------------------------------------------
def apply_permutation(perm: jax.Array, *cols: jax.Array):
    """Materialize a planned permutation: out[i] = col[perm[i]] per column —
    one gather per column, the entire per-column transform cost.

    Returns a single array for one column, a tuple for several (sort_pairs
    idiom)."""
    outs = tuple(jnp.take(c, perm, axis=0) for c in cols)
    return outs if len(cols) != 1 else outs[0]


def plan_sort_permutation(keys: jax.Array, *, impl: str | None = None):
    """Plan a stable key sort once, payloads later.

    Returns (sorted_keys, perm) where perm is the composed gather map:
    `apply_permutation(perm, col)` equals `sort_pairs(keys, col)[1]` for any
    payload column, without re-sorting.

    impl='xla' (default): XLA's tuned native sort — the deliberate
    production arm for full key sorts, mirroring the paper's use of the
    vendor SORT-PAIRS (§2.3). impl='radix': the kernel-backed sort-free
    rank passes over the full key pattern (int32 keys), equal to the XLA
    sort bit-for-bit; flip `DEFAULT_SORT_PLAN_IMPL` (or pass impl=) to run
    SMJ's GFTR transform entirely sort-free on radix hardware."""
    from repro.kernels import ops as kops

    impl = DEFAULT_SORT_PLAN_IMPL if impl is None else impl
    return kops.sort_plan(keys, impl)


def plan_partition_permutation(digits: jax.Array, num_partitions: int, *,
                               max_pass_bits: int | None = None,
                               carry: Sequence[jax.Array] = (),
                               impl: str | None = None):
    """Plan a stable radix partition once, payloads later.

    Returns (perm, offsets, sizes) — or (perm, carried, offsets, sizes) when
    `carry` is non-empty — with all layout arrays int32:
      perm[j]    = source row landing at output position j (gather form)
      offsets[p] = first output position of partition p
      sizes[p]   = rows in partition p

    impl='pallas' (the default, via `kernels.ops.PARTITION_PLAN_IMPL`) runs
    the sort-free rank pipeline: per-pass histogram -> exclusive prefix ->
    stable ranks, LSD-composed for fan-outs past one pass — linear work per
    pass, zero XLA sort primitives (jaxpr-pinned). PHJ, the partition
    group-by, multi_pass_radix_partition, and the fused group-join all ride
    it through this one entry point. impl='xla' keeps the stable-sort
    reference arm: `max_pass_bits=None` computes the permutation with one
    XLA stable sort; an integer runs the paper's multi-pass structure —
    stable passes of <= max_pass_bits bits, LSD order — and composes them
    into the same single permutation (equality is the §4.3 stability
    argument; both arms are parity-tested in tests/test_permutation.py).
    Either way, payload columns cost one `apply_permutation` gather each,
    never one gather per pass.

    `carry` columns come back already partitioned (Algorithm 1's
    key-rides-along idiom): the XLA arm carries them through its sort, the
    rank arm materializes each with one gather through the composed
    permutation — same contract, same values. Carry the column(s) the next
    phase reads immediately (e.g. the group key)."""
    from repro.kernels import ops as kops

    impl = kops.partition_plan_impl() if impl is None else impl
    perm, carried, offsets, sizes = kops.partition_plan(
        digits, num_partitions, carry=carry, max_pass_bits=max_pass_bits,
        impl=impl)
    if carry:
        return perm, carried, offsets, sizes
    return perm, offsets, sizes


# ---------------------------------------------------------------------------
# RADIX-PARTITION
# ---------------------------------------------------------------------------
def radix_digits(keys: jax.Array, start_bit: int, num_bits: int) -> jax.Array:
    """Extract the radix digit (bits [start_bit, start_bit+num_bits))."""
    mask = (1 << num_bits) - 1
    return (
        (keys.astype(jnp.uint32 if keys.dtype.itemsize <= 4 else jnp.uint64) >> start_bit)
        & mask
    ).astype(jnp.int32)


def partition_permutation(digits: jax.Array, num_partitions: int):
    """Stable-partition permutation & layout for given digits.

    Returns (perm, offsets, sizes):
      perm[j]    = source row that lands at output position j (gather form)
      offsets[p] = first output position of partition p (exclusive prefix sum)
      sizes[p]   = number of rows in partition p

    Deterministic by construction (stable sort on digit) — this is the TPU
    equivalent of the paper's §4.3 requirement that partitioning be stable so
    the same permutation applies to every payload column.

    offsets/sizes are int32 on every path (the Pallas rank kernel, the XLA
    ref, and this planner agree — see tests/test_permutation.py).
    """
    return plan_partition_permutation(digits, num_partitions)


def radix_partition(
    keys: jax.Array,
    *values: jax.Array,
    start_bit: int,
    num_bits: int,
):
    """RADIX-PARTITION primitive: stable partition of (keys, values...) by the
    radix digit. Partitions are stored contiguously (no fragmentation, unlike
    bucket chaining — paper §4.3). Returns (keys_out, *values_out, offsets,
    sizes)."""
    digits = radix_digits(keys, start_bit, num_bits)
    perm, offsets, sizes = partition_permutation(digits, 1 << num_bits)
    outs = tuple(jnp.take(a, perm, axis=0) for a in (keys,) + values)
    return outs + (offsets, sizes)


def multi_pass_radix_partition(
    keys: jax.Array,
    *values: jax.Array,
    total_bits: int,
    start_bit: int = 0,
):
    """Multi-pass RADIX-PARTITION (paper §3.2/§4.3: >256 partitions require
    multiple passes of <=8 bits). LSD order: later passes use higher bits, and
    stability makes the composition a single stable partition on all
    `total_bits` bits.

    One-permutation materialization: the passes carry only (digit, iota) and
    compose into a single permutation; every column — key and payloads alike
    — is then gathered exactly once, instead of once per pass (which made
    wide partitions cost O(passes * C) materializations).

    Returns (keys_out, *values_out, offsets, sizes) for the full fan-out.
    """
    digits = radix_digits(keys, start_bit, total_bits)
    perm, offsets, sizes = plan_partition_permutation(
        digits, 1 << total_bits, max_pass_bits=RADIX_BITS_PER_PASS
    )
    outs = apply_permutation(perm, keys, *values)
    if not values:
        outs = (outs,)
    return outs + (offsets, sizes)


def num_radix_passes(total_bits: int) -> int:
    """Pass count for the analytic cost model (paper: 15-16 bits -> 2 passes)."""
    return -(-total_bits // RADIX_BITS_PER_PASS)


def radix_sort_pairs(keys: jax.Array, *values: jax.Array, key_bits: int | None = None):
    """Paper-faithful LSD radix sort built from stable RADIX-PARTITION passes
    (8 bits per pass — CUB SORT-PAIRS' structure, §4.2's '17 sequential
    passes' cost shape). Non-negative keys. Equivalent to sort_pairs; the
    production path uses XLA's sort, this one exists so the pass structure
    the cost model charges for is real, executable code."""
    if key_bits is None:
        key_bits = 8 * keys.dtype.itemsize - 1  # non-negative keys
    arrs = (keys,) + values
    bit = 0
    while bit < key_bits:
        bits = min(RADIX_BITS_PER_PASS, key_bits - bit)
        res = radix_partition(arrs[0], *arrs[1:], start_bit=bit, num_bits=bits)
        arrs = res[:-2]
        bit += bits
    return arrs if values else arrs[0]


# ---------------------------------------------------------------------------
# GATHER
# ---------------------------------------------------------------------------
def gather(src: jax.Array, idx: jax.Array, *, fill=None) -> jax.Array:
    """GATHER primitive: out[i] = src[idx[i]]; idx < 0 or >= len -> fill (if
    given) else clipped. Whether this is clustered or unclustered depends
    entirely on `idx` — the paper's central observation."""
    out = jnp.take(src, jnp.clip(idx, 0, src.shape[0] - 1), axis=0)
    if fill is not None:
        valid = (idx >= 0) & (idx < src.shape[0])
        out = jnp.where(valid.reshape(valid.shape + (1,) * (out.ndim - 1)), out, fill)
    return out


def clustered_gather(src: jax.Array, idx: jax.Array) -> jax.Array:
    """GATHER through a clustered map — GFTR's second gather, from a
    transformed relation: out[i] = src[idx[i]], 0 where idx is out of range
    (`gather(..., fill=0)`). Runs the windowed VMEM kernel where the
    backend compiles Pallas, XLA's take otherwise
    (`kernels.ops.clustered_gather`)."""
    from repro.kernels import ops as kops

    return kops.clustered_gather(src, idx)


def histogram(x: jax.Array, num_bins: int) -> jax.Array:
    return jnp.bincount(x, length=num_bins)


# ---------------------------------------------------------------------------
# Compaction (static-capacity stream compaction)
# ---------------------------------------------------------------------------
def compact(mask: jax.Array, arrays: Sequence[jax.Array], capacity: int, fill=0):
    """Stable stream compaction: rows where mask is True are moved to the
    front (preserving order) of capacity-sized outputs; returns
    (compacted_arrays, valid_count). Rows beyond `capacity` are dropped.

    Stability matters: it preserves the clustering of tuple-ID columns that
    GFTR relies on (monotone inputs stay monotone).
    """
    n = mask.shape[0]
    pos = jnp.cumsum(mask.astype(jnp.int32)) - 1  # output slot per valid row
    count = jnp.minimum(pos[-1] + 1 if n else 0, capacity)
    dest = jnp.where(mask & (pos < capacity), pos, capacity)  # OOB -> dropped
    outs = []
    for a in arrays:
        out = jnp.full((capacity + 1,) + a.shape[1:], fill, a.dtype)
        out = out.at[dest].set(a, mode="drop")
        outs.append(out[:capacity])
    return outs, count


def expand_offsets(counts: jax.Array, capacity: int):
    """Expansion helper for m:n matches: given per-row match counts, returns
    (row_of_output, rank_within_row, valid, total) for `capacity` output rows.

    out t belongs to input row j = max{j : offsets[j] <= t} and is its
    (t - offsets[j])-th match.
    """
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts.astype(jnp.int32))]
    )
    total = offsets[-1]
    t = jnp.arange(capacity, dtype=jnp.int32)
    row = jnp.searchsorted(offsets, t, side="right").astype(jnp.int32) - 1
    rank = t - offsets[jnp.clip(row, 0, counts.shape[0] - 1)]
    valid = t < total
    return jnp.clip(row, 0, counts.shape[0] - 1), rank, valid, total
