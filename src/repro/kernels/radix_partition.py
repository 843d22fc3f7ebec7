"""Stable radix-partition rank kernels and the sort-free partition planner
(RADIX-PARTITION primitive, §2.3/§4.3).

Single pass — the classic GPU partitioning pipeline (He et al. SIGMOD'08;
Sioulas et al. ICDE'19), with prefix sums instead of atomics (deterministic
by construction — the property PHJ-OM needs):

  pass A (histogram): per-block digit histograms -> (num_blocks, G)
  host:   exclusive prefix over blocks & digits -> per-block base offsets
  pass B (rank):      per-element destination index
            dest[i] = base[block, digit] + rank_within_block(i)

The within-block stable rank is a running count over the one-hot digit
expansion of each 128-lane row (bins along sublanes), with the in-row prefix
taken by one 0/1 matmul — dense VPU/MXU work; no scatter ever happens inside
a kernel. The actual data movement is then a single XLA gather with the
inverted permutation.

Multi-pass (`partition_plan_pallas`): fan-outs past one pass's bin budget
compose LSD passes of <= `pass_bits` bits each — pass k ranks bits
[k*b, (k+1)*b) of the digit over the order left by pass k-1, and stability
makes the composition equal the single stable partition on all bits (the
§4.3 argument, property-tested against the sort-based XLA arm). Each pass
is O(n * 2^pass_bits) dense work plus one n-sized scatter to fold the
pass's destinations into the running permutation; no comparison sort
anywhere, so the whole plan is linear in n.

Interpret-mode note: off-TPU the per-pass ranks run as the kernel's own
arithmetic in straight-line jnp (`pass_impl="dense"` — `digit_onehot` +
cumsum, exactly the kernel body without the pallas_call emulation overhead);
on TPU the compiled two-kernel pipeline runs (`pass_impl="kernel"`). Both
arms are parity-tested against each other and the sort-based reference.
"""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl
import jax.numpy as jnp

from .common import (LANES, ceil_div, digit_lane_blocks, digit_onehot, lane_onehot,
                     resolve_interpret, tile_onehot_sum)


def _block_hist_kernel(num_bins: int, x_ref, o_ref):
    # grid step i owns column i % 128 of the (num_bins, 128) output block
    # shared by 128 consecutive steps (block-histograms are stored
    # transposed: digits along sublanes, blocks along lanes)
    i = pl.program_id(0)

    @pl.when(i % LANES == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    acc = tile_onehot_sum(x_ref, num_bins, jnp.zeros(o_ref.shape, jnp.int32))
    col = acc.sum(axis=1, keepdims=True)  # (num_bins, 1)
    mine = jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 1) == i % LANES
    o_ref[...] = jnp.where(mine, col, o_ref[...])


def _block_histograms_t(d2, num_bins: int, block_rows: int, interpret: bool):
    """(num_bins, grid rounded up to 128) per-block histograms of the lane
    blocks `d2`, transposed; columns past the grid are zero."""
    grid = d2.shape[0] // block_rows
    return pl.pallas_call(
        functools.partial(_block_hist_kernel, num_bins),
        grid=(grid,),
        in_specs=[pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((num_bins, LANES), lambda i: (0, i // LANES)),
        out_shape=jax.ShapeDtypeStruct(
            (num_bins, ceil_div(grid, LANES) * LANES), jnp.int32),
        interpret=interpret,
    )(d2)


def block_histograms_pallas(
    digits: jax.Array, num_bins: int, *, block_rows: int = 64,
    interpret: bool | None = None,
) -> jax.Array:
    """(num_blocks, num_bins) per-block histograms. Padding rows (PAD_DIGIT)
    are excluded by construction — `lane_onehot` masks negative digits out
    of the one-hot, so no fill value can ever be counted into a bin."""
    d2 = digit_lane_blocks(digits, block_rows)
    bh = _block_histograms_t(d2, num_bins, block_rows,
                             resolve_interpret(interpret))
    return bh[:, :d2.shape[0] // block_rows].T


def _rank_kernel(num_bins: int, x_ref, base_ref, o_ref):
    i = pl.program_id(0)
    G = num_bins
    # this block's base offsets: column i % 128 of the transposed base block,
    # kept broadcast over lanes as the running per-digit position
    mine = jax.lax.broadcasted_iota(jnp.int32, base_ref.shape, 1) == i % LANES
    start = jnp.where(mine, base_ref[...], 0).sum(axis=1, keepdims=True)
    # [strict upper triangle | ones]: one matmul gives each row's exclusive
    # within-row rank per digit (lanes 0..127) and the row's digit totals
    # broadcast over lanes (lanes 128..255); 0/1 inputs, counts <= 128, so
    # bf16 operands with f32 accumulation are exact
    src = jax.lax.broadcasted_iota(jnp.int32, (LANES, 2 * LANES), 0)
    dst = jax.lax.broadcasted_iota(jnp.int32, (LANES, 2 * LANES), 1)
    scan_op = ((src < dst) | (dst >= LANES)).astype(jnp.bfloat16)

    def row(r, pos):
        x = x_ref[pl.ds(r, 1), :]  # (1, 128)
        oh = lane_onehot(x, G)  # (G, 128); pad lanes all-zero
        pt = jnp.dot(oh.astype(jnp.bfloat16), scan_op,
                     preferred_element_type=jnp.float32).astype(jnp.int32)
        # own-digit selection without gather: masked sublane sum
        dest = jnp.where(oh > 0, pos + pt[:, :LANES], 0).sum(axis=0,
                                                             keepdims=True)
        o_ref[pl.ds(r, 1), :] = jnp.where(x >= 0, dest, -1)
        return pos + pt[:, LANES:]

    jax.lax.fori_loop(0, x_ref.shape[0], row,
                      jnp.broadcast_to(start, (G, LANES)))


def partition_ranks_pallas(
    digits: jax.Array,
    num_bins: int,
    *,
    block_rows: int = 64,
    interpret: bool | None = None,
):
    """Destination index per element for the stable partition (one pass).

    Returns (dest, offsets, sizes): dest[i] = output position of element i;
    offsets/sizes describe the contiguous partition layout. Negative digits
    (PAD_DIGIT padding) get dest -1 and never occupy a position."""
    n = digits.shape[0]
    interpret = resolve_interpret(interpret)
    d2 = digit_lane_blocks(digits, block_rows)
    grid = d2.shape[0] // block_rows
    bh = _block_histograms_t(d2, num_bins, block_rows, interpret)
    sizes = bh.sum(axis=1)
    offsets = (jnp.cumsum(sizes) - sizes).astype(jnp.int32)
    # base[g, b] = offsets[g] + sum_{b' < b} bh[g, b']
    base = (offsets[:, None] + jnp.cumsum(bh, axis=1) - bh).astype(jnp.int32)
    dest = pl.pallas_call(
        functools.partial(_rank_kernel, num_bins),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((num_bins, LANES), lambda i: (0, i // LANES)),
        ],
        out_specs=pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((d2.shape[0], LANES), jnp.int32),
        interpret=interpret,
    )(d2, base)
    return dest.reshape(-1)[:n], offsets, sizes


# ---------------------------------------------------------------------------
# Sort-free multi-pass planner
# ---------------------------------------------------------------------------
def _dense_pass_dest(digits: jax.Array, num_bins: int) -> jax.Array:
    """The rank kernels' arithmetic as straight-line jnp — histogram, digit
    prefix, and stable within-digit rank from one masked one-hot cumsum.
    This is the interpret-mode arm of a plan pass: identical math to
    `partition_ranks_pallas` (same `digit_onehot` core) without the
    pallas_call emulation overhead."""
    oh = digit_onehot(digits, num_bins)  # (n, G)
    excl = jnp.cumsum(oh, axis=0) - oh  # exclusive within-digit rank
    sizes = excl[-1] + oh[-1] if digits.shape[0] else jnp.zeros(
        (num_bins,), jnp.int32)
    offsets = (jnp.cumsum(sizes) - sizes).astype(jnp.int32)
    rank = (excl * oh).sum(axis=1)
    base = jnp.take(offsets, jnp.clip(digits, 0, num_bins - 1))
    return jnp.where(digits >= 0, base + rank, -1)


def pass_dest(digits: jax.Array, num_bins: int, *,
              pass_impl: str = "auto", block_rows: int = 8,
              interpret: bool | None = None) -> jax.Array:
    """One stable partition pass: destination per element for `num_bins`
    digits. pass_impl: 'kernel' forces the two-kernel pallas pipeline,
    'dense' the straight-line jnp twin, 'auto' picks dense under interpret
    mode (same math, no emulation overhead) and the kernels on TPU."""
    interpret = resolve_interpret(interpret)
    if pass_impl == "auto":
        pass_impl = "dense" if interpret else "kernel"
    if pass_impl == "dense":
        return _dense_pass_dest(digits.astype(jnp.int32), num_bins)
    dest, _, _ = partition_ranks_pallas(
        digits.astype(jnp.int32), num_bins, block_rows=block_rows,
        interpret=interpret)
    return dest


def _compose_lsd(extract_digit, n: int, total_bits: int, pass_bits: int,
                 tail_mask=None, *, pass_impl: str = "auto",
                 interpret: bool | None = None) -> jax.Array:
    """Compose stable LSD passes into one gather-form permutation.

    extract_digit(perm, bit, bits) must return the pass digits IN CURRENT
    ORDER (i.e. of source rows perm[0..n)). `tail_mask`, when given, marks
    rows of a dedicated trailing class (the planner's sentinel partition):
    each pass ranks them into one extra bin past the bit bins, which keeps
    them stably behind every real digit without widening the bit passes.

    Each pass costs one rank computation plus one n-sized scatter — the
    inversion that folds the pass's scatter-form destinations into the
    running gather-form permutation. No sort primitive anywhere."""
    iota = jnp.arange(n, dtype=jnp.int32)
    perm = iota
    bit = 0
    first = True
    while first or bit < total_bits:
        bits = min(pass_bits, max(total_bits - bit, 0))
        nb = (1 << bits) + (1 if tail_mask is not None else 0)
        pd = extract_digit(perm, bit, bits)
        if tail_mask is not None:
            tm = tail_mask if first else jnp.take(tail_mask, perm)
            pd = jnp.where(tm, nb - 1, pd)
        dest = pass_dest(pd, nb, pass_impl=pass_impl, interpret=interpret)
        perm = jnp.zeros((n,), jnp.int32).at[dest].set(perm, mode="drop")
        bit += bits
        first = False
    return perm


def partition_plan_pallas(
    digits: jax.Array,
    num_partitions: int,
    *,
    carry=(),
    max_pass_bits: int | None = None,
    pass_impl: str = "auto",
    interpret: bool | None = None,
):
    """Sort-free stable partition plan: histogram -> prefix -> rank passes,
    LSD-composed for any fan-out. Drop-in producer of the planner contract:

    Returns (perm, carried, offsets, sizes), all layout arrays int32:
      perm[j]    = source row landing at output position j (gather form)
      offsets[p] = first output position of partition p
      sizes[p]   = rows in partition p

    digits must lie in [0, num_partitions). Carried columns are materialized
    with one gather through the composed permutation each (they cannot ride
    the rank passes, which move no payload bytes at all — that is the point);
    the contract and values match the XLA reference arm exactly.

    When num_partitions-1 crosses a pass boundary that num_partitions-2 does
    not (the group-by planner's 2^k+1 layout, whose last partition swallows
    sentinel padding), the top partition is ranked as a dedicated tail class
    inside each pass instead of paying an extra whole pass for one bin.

    offsets come from a binary search over the partitioned digits (they are
    sorted by construction after the final pass) — no bincount scatter, no
    sort."""
    n = digits.shape[0]
    digits = digits.astype(jnp.int32)
    interpret = resolve_interpret(interpret)
    # 8-bit passes on TPU (the paper's Ampere bound); 4-bit in interpret
    # mode, where a pass is O(n * bins) dense work and smaller bins win.
    pb = 4 if (interpret and pass_impl != "kernel") else 8
    if max_pass_bits is not None:
        pb = max(1, min(pb, max_pass_bits))
    B = num_partitions
    full_bits = max(1, (B - 1).bit_length())
    tail_bits = max((B - 2).bit_length(), 0) if B >= 2 else 0
    use_tail = B >= 2 and ceil_div(tail_bits, pb) < ceil_div(full_bits, pb)
    tail_mask = (digits == B - 1) if use_tail else None
    total_bits = tail_bits if use_tail else full_bits

    def extract(perm, bit, bits):
        cur = digits if bit == 0 else jnp.take(digits, perm)
        return (cur >> bit) & ((1 << bits) - 1)

    perm = _compose_lsd(extract, n, total_bits, pb, tail_mask,
                        pass_impl=pass_impl, interpret=interpret)
    dsort = jnp.take(digits, perm)  # sorted by construction
    offsets = jnp.searchsorted(
        dsort, jnp.arange(B, dtype=jnp.int32), side="left").astype(jnp.int32)
    sizes = jnp.diff(jnp.concatenate(
        [offsets, jnp.full((1,), n, jnp.int32)])).astype(jnp.int32)
    carried = tuple(jnp.take(c, perm, axis=0) for c in carry)
    return perm, carried, offsets, sizes


def sort_plan_radix(keys: jax.Array, *, pass_impl: str = "auto",
                    interpret: bool | None = None):
    """Sort-free stable sort plan over full integer keys: LSD rank passes
    over the sign-biased 32-bit pattern. Returns (sorted_keys, perm) with
    the `plan_sort_permutation` contract; equals the XLA stable sort
    exactly (parity-tested). int32/uint32 keys only — the radix arm exists
    for radix-hardware parity and fully sort-free pipelines; XLA's tuned
    sort remains the default production arm (§2.3)."""
    if keys.dtype not in (jnp.int32, jnp.uint32):
        raise TypeError(f"radix sort plan needs (u)int32 keys, got {keys.dtype}")
    n = keys.shape[0]
    # signed keys: xor the sign bit so unsigned digit order equals signed
    # key order; unsigned keys are already in digit order
    bias = jnp.uint32(0x80000000 if keys.dtype == jnp.int32 else 0)
    u = keys.astype(jnp.uint32) ^ bias
    interpret = resolve_interpret(interpret)
    pb = 4 if (interpret and pass_impl != "kernel") else 8

    def extract(perm, bit, bits):
        cur = u if bit == 0 else jnp.take(u, perm)
        return ((cur >> bit) & ((1 << bits) - 1)).astype(jnp.int32)

    perm = _compose_lsd(extract, n, 32, pb, None, pass_impl=pass_impl,
                        interpret=interpret)
    return jnp.take(keys, perm), perm
