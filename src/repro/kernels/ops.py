"""Public jit'd wrappers + kernel/XLA dispatch for the Pallas kernels.

Dispatch policy mirrors the paper's planner logic: the windowed (clustered)
kernels are only profitable/correct when the gather map / merge frontier is
clustered, so each wrapper measures the per-tile span (cheap, O(n/tile)) and
falls back to XLA's random-access path otherwise. `clustered_gather` needs
no such choice: its kernel is exact for any index and costs one VMEM
window per window of each tile's span, measured on the device.

Execution mode is resolved per call (`common.resolve_interpret`): compiled
kernels on TPU, interpret mode elsewhere; REPRO_PALLAS_INTERPRET=0/1
overrides either way, and takes effect immediately — nothing is frozen at
import time.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.resilience import faults

from . import ref
from .common import ceil_div, default_interpret
from .gather import gather_windowed_pallas
from .hash_probe import hash_probe_pallas, layout_probe_blocks, probe_agg_pallas
from .histogram import histogram_pallas
from .merge_join import lower_bound_windowed_pallas
from .radix_partition import partition_plan_pallas, partition_ranks_pallas, sort_plan_radix
from .segsum import segsum_partials_pallas

# Production arm of the partition planner (core.primitives resolves its
# impl=None through this): 'pallas' = the sort-free histogram/rank pipeline,
# 'xla' = the stable-sort reference. Env knob for A/B and bisection; read
# and validated per call (never frozen at import), so an unknown value
# raises instead of silently running an arm the cost model never priced.
PARTITION_PLAN_IMPLS = ("pallas", "xla")


def partition_plan_impl() -> str:
    env = os.environ.get("REPRO_PARTITION_PLAN_IMPL", "pallas")
    if env not in PARTITION_PLAN_IMPLS:
        raise ValueError(
            f"REPRO_PARTITION_PLAN_IMPL={env!r} is not a recognized value; "
            f"allowed: {'/'.join(PARTITION_PLAN_IMPLS)}")
    return env


def __getattr__(name):  # keep the old constant's spelling working
    if name == "PARTITION_PLAN_IMPL":
        return partition_plan_impl()
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

KEY_SENTINEL = -1


def _pallas_arm(site: str, pallas_fn, xla_fn):
    """Run a dispatch's pallas arm; an armed `REPRO_FAULTS=pallas:<site>`
    injection instead runs the bit-identical XLA arm and records the event
    (DESIGN.md §13's pallas -> xla chain; the dense-jnp reference IS the xla
    arm here, so the chain terminates). A real kernel failure — one that
    does not lower or compile for the device — propagates: silently running
    the reference would hide that the device path is broken.

    Zero-overhead contract: with no faults active this is one host-side
    call through `pallas_fn` — the fault check contributes nothing to the
    traced jaxpr."""
    try:
        faults.check_pallas(site)
    except faults.FaultInjected as e:
        from repro.obs import metrics  # deferred: kernels stay obs-free
        from repro.resilience import escalation

        metrics.counter("resilience.kernel_fallbacks").inc()
        metrics.counter(f"resilience.kernel_fallbacks.{site}").inc()
        escalation.record_degradation(
            f"kernels.{site}", f"pallas arm failed: {type(e).__name__}: {e}")
        return xla_fn()
    return pallas_fn()


# ---------------------------------------------------------------------------
# histogram / partition ranks
# ---------------------------------------------------------------------------
def histogram(digits: jax.Array, num_bins: int, impl: str = "pallas") -> jax.Array:
    if impl == "pallas":
        return _pallas_arm(
            "histogram",
            lambda: histogram_pallas(digits, num_bins, interpret=None),
            lambda: ref.histogram(digits, num_bins))
    return ref.histogram(digits, num_bins)


def _partition_ranks_xla(digits, num_bins):
    dest = ref.partition_ranks(digits, num_bins)
    sz = ref.histogram(digits, num_bins)
    off = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(sz)[:-1].astype(jnp.int32)])
    return dest, off, sz


def partition_ranks(digits: jax.Array, num_bins: int, impl: str = "pallas"):
    """dest position per element (stable partition)."""
    if impl == "pallas":
        return _pallas_arm(
            "partition_ranks",
            lambda: partition_ranks_pallas(digits, num_bins, interpret=None),
            lambda: _partition_ranks_xla(digits, num_bins))
    return _partition_ranks_xla(digits, num_bins)


# ---------------------------------------------------------------------------
# partition / sort planning (one-permutation layer backends)
# ---------------------------------------------------------------------------
def partition_plan(digits: jax.Array, num_partitions: int, *, carry=(),
                   max_pass_bits: int | None = None, impl: str = "pallas",
                   pass_impl: str = "auto"):
    """Stable-partition plan: (perm, carried, offsets, sizes), all layout
    arrays int32. The production entry behind
    `core.primitives.plan_partition_permutation`.

    impl='pallas': the sort-free rank pipeline (per-pass histogram ->
    block/digit exclusive prefix -> stable ranks, LSD-composed past one
    pass's bin budget) — O(n) per pass, zero sort primitives in the jaxpr.
    impl='xla': the stable-sort reference arm (the previous production
    path), kept for parity testing and as the conservative fallback;
    `max_pass_bits` there runs the paper's multi-pass composition with
    sorts standing in for the rank passes.

    Both arms return bit-identical results — the stable partition
    permutation is unique (tests/test_permutation.py pins the parity)."""
    if impl == "pallas":
        return _pallas_arm(
            "partition_plan",
            lambda: partition_plan_pallas(
                digits, num_partitions, carry=carry,
                max_pass_bits=max_pass_bits, pass_impl=pass_impl,
                interpret=None),
            lambda: _partition_plan_xla(digits, num_partitions, carry,
                                        max_pass_bits))
    if impl != "xla":
        raise ValueError(f"unknown partition plan impl {impl!r}")
    return _partition_plan_xla(digits, num_partitions, carry, max_pass_bits)


def _partition_plan_xla(digits, num_partitions, carry, max_pass_bits):
    n = digits.shape[0]
    digits = digits.astype(jnp.int32)
    iota = jnp.arange(n, dtype=jnp.int32)
    if max_pass_bits is None:
        res = jax.lax.sort((digits,) + tuple(carry) + (iota,), num_keys=1,
                           is_stable=True)
        carried, perm = res[1:-1], res[-1]
    else:
        total_bits = max(1, int(num_partitions - 1).bit_length())
        perm = iota
        cur = digits
        carried = tuple(carry)
        bit = 0
        while bit < total_bits:
            bits = min(max_pass_bits, total_bits - bit)
            sub = (cur >> bit) & ((1 << bits) - 1)
            res = jax.lax.sort((sub, cur) + carried + (perm,), num_keys=1,
                               is_stable=True)
            cur, carried, perm = res[1], res[2:-1], res[-1]
            bit += bits
    sizes = jnp.bincount(digits, length=num_partitions).astype(jnp.int32)
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(sizes)[:-1].astype(jnp.int32)]
    )
    return perm, carried, offsets, sizes


def sort_plan(keys: jax.Array, impl: str = "xla"):
    """Stable sort plan: (sorted_keys, perm). impl='xla' is the production
    arm (XLA's tuned sort — the paper's vendor-primitive choice, §2.3);
    impl='radix' composes the same sort-free rank passes over the full
    sign-biased key pattern (int32 keys), for radix-hardware parity and
    fully sort-free pipelines."""
    if impl == "radix":
        return _pallas_arm(
            "sort_plan",
            lambda: sort_plan_radix(keys, interpret=None),
            lambda: _sort_plan_xla(keys))
    if impl != "xla":
        raise ValueError(f"unknown sort plan impl {impl!r}")
    return _sort_plan_xla(keys)


def _sort_plan_xla(keys):
    iota = jnp.arange(keys.shape[0], dtype=jnp.int32)
    sk, perm = jax.lax.sort((keys, iota), num_keys=1, is_stable=True)
    return sk, perm


def apply_partition(dest: jax.Array, *arrays: jax.Array):
    """Materialize the partition: invert dest (scatter of iota) and gather.
    The kernel computes ranks; XLA moves the bytes (DESIGN.md §2)."""
    n = dest.shape[0]
    inv = jnp.zeros((n,), jnp.int32).at[jnp.clip(dest, 0, n - 1)].set(
        jnp.arange(n, dtype=jnp.int32), mode="drop"
    )
    return tuple(jnp.take(a, inv, axis=0) for a in arrays)


# ---------------------------------------------------------------------------
# merge lower bound
# ---------------------------------------------------------------------------
def merge_lower_bound(
    build_sorted: jax.Array,
    probe_sorted: jax.Array,
    impl: str = "auto",
    *,
    window_rows: int = 1024,
    tile: int = 1024,
):
    """lower bound of each (sorted) probe key in the sorted build keys.

    impl='auto' checks tile spans eagerly (concrete values required);
    'pallas' forces the windowed kernel; 'xla' forces searchsorted."""
    if impl == "xla":
        return ref.lower_bound(build_sorted, probe_sorted)
    n_p = probe_sorted.shape[0]
    n_tiles = ceil_div(n_p, tile)
    firsts = probe_sorted[:: tile]
    coarse = jnp.searchsorted(build_sorted, firsts, side="left").astype(jnp.int32)
    win_idx = coarse // window_rows
    if impl == "auto":
        # span check: lb range covered by each tile's 2W window?
        lasts = probe_sorted[jnp.minimum(jnp.arange(n_tiles) * tile + tile - 1, n_p - 1)]
        coarse_hi = jnp.searchsorted(build_sorted, lasts, side="left").astype(jnp.int32)
        fits = bool(jnp.all(coarse_hi < (win_idx + 2) * window_rows))
        if not fits:
            return ref.lower_bound(build_sorted, probe_sorted)
    return _pallas_arm(
        "merge_lower_bound",
        lambda: lower_bound_windowed_pallas(
            build_sorted, probe_sorted, win_idx,
            window_rows=window_rows, tile=tile, interpret=None),
        lambda: ref.lower_bound(build_sorted, probe_sorted))


# ---------------------------------------------------------------------------
# hash probe
# ---------------------------------------------------------------------------
def hash_probe(
    bkeys: jax.Array,
    off_r: jax.Array,
    probe_keys_part: jax.Array,
    probe_off: jax.Array,
    probe_sz: jax.Array,
    impl: str = "pallas",
):
    """Co-partition PK-FK probe over a partitioned probe side.

    Returns (vid_r, matched) aligned with probe_keys_part order."""
    P, cap_r = bkeys.shape
    n = probe_keys_part.shape[0]

    def xla_arm():
        # reconstruct per-row partition ids from the layout. Rows past the
        # last real partition (a sentinel partition's overhang) still map to
        # P - 1; their keys are KEY_SENTINEL so they can never match.
        row = jnp.arange(n, dtype=jnp.int32)
        part = jnp.clip(
            jnp.searchsorted(probe_off, row, side="right").astype(jnp.int32) - 1, 0, P - 1
        )
        return ref.hash_probe_blocks(bkeys, off_r, probe_keys_part, part)

    if impl == "xla":
        return xla_arm()

    def pallas_arm():
        cap_s = cap_r
        max_blocks = ceil_div(n, cap_s) + P
        pk, part, src_idx = layout_probe_blocks(probe_keys_part, probe_off, probe_sz, cap_s, max_blocks)
        vid, hit = hash_probe_pallas(bkeys, off_r, pk, part, interpret=None)
        # scatter sub-block results back to partitioned probe order
        flat_src = src_idx.reshape(-1)
        ok = flat_src >= 0
        vid_out = jnp.full((n,), -1, jnp.int32).at[jnp.where(ok, flat_src, n)].set(
            vid.reshape(-1), mode="drop"
        )
        hit_out = jnp.zeros((n,), jnp.int32).at[jnp.where(ok, flat_src, n)].set(
            hit.reshape(-1), mode="drop"
        )
        return vid_out, hit_out.astype(bool)

    return _pallas_arm("hash_probe", pallas_arm, xla_arm)


# ---------------------------------------------------------------------------
# fused probe + accumulate (group-join)
# ---------------------------------------------------------------------------
def _combine_group_partials(pk, ps_cols, pc, num_groups, key_dtype):
    """Sorted segmented combine of per-tile (key, sums..., count) partials
    into the dense (keys, sums (C, G), counts, n_found) accumulator contract
    — the same combine shape as groupby_sorted_sum, carrying counts and any
    number of sum columns through ONE sort."""
    sk, sc, *ss = jax.lax.sort((pk, pc) + tuple(ps_cols), num_keys=1,
                               is_stable=True)
    valid = sk != KEY_SENTINEL
    bnd = jnp.concatenate([jnp.ones((1,), bool), sk[1:] != sk[:-1]]) & valid
    gid = jnp.cumsum(bnd.astype(jnp.int32)) - 1
    n_found = gid[-1] + 1
    gid = jnp.where(valid & (gid < num_groups), gid, num_groups)
    keys_o = jnp.full((num_groups + 1,), KEY_SENTINEL, key_dtype).at[gid].set(
        jnp.where(valid, sk, KEY_SENTINEL), mode="drop"
    )
    sums_o = jnp.stack([
        jax.ops.segment_sum(jnp.where(valid, s, 0.0), gid,
                            num_segments=num_groups + 1)[:num_groups]
        for s in ss
    ]) if ss else jnp.zeros((0, num_groups), jnp.float32)
    counts_o = jax.ops.segment_sum(jnp.where(valid, sc, 0), gid,
                                   num_segments=num_groups + 1)
    return (keys_o[:num_groups], sums_o, counts_o[:num_groups],
            jnp.minimum(n_found, num_groups))


def groupjoin_probe_agg(
    bkeys: jax.Array,  # (P, capR) padded build key blocks
    bvals: jax.Array | None,  # (P, Cb, capR) build value blocks, None if none
    off_r: jax.Array,  # (P,) build partition offsets
    probe_keys_part: jax.Array,  # partitioned probe join keys
    gk_part: jax.Array,  # partitioned probe group keys
    pv_part: jax.Array | None,  # (Cp, n) partitioned probe value columns
    probe_off: jax.Array,
    probe_sz: jax.Array,
    num_groups: int,
    *,
    col_sides: tuple,  # ("probe"|"build", within-side index) per sum column
    impl: str = "pallas",
):
    """Co-partition pk_fk probe fused with grouped accumulation: each probe
    sub-block is matched against its build block ONCE and reduced to
    per-tile (group key, sums..., count) partials in VMEM — the joined rows
    are never written, and every aggregate column rides the same probe pass
    — then one sorted segmented combine produces the accumulator.

    Returns (group_keys[num_groups], sums[C, num_groups],
    counts[num_groups], valid_count)."""
    P, cap_r = bkeys.shape
    n = probe_keys_part.shape[0]
    count_only = not col_sides
    if count_only:  # keys+counts still flow through one (dummy) sum column
        col_sides = (("probe", 0),)
    if bvals is None:
        bvals = jnp.zeros((P, 1, cap_r), jnp.float32)
    if pv_part is None:
        pv_part = jnp.zeros((1, n), jnp.float32)
    def xla_arm():
        # reference arm: plain probe, then per-row values + segmented combine
        row = jnp.arange(n, dtype=jnp.int32)
        part = jnp.clip(
            jnp.searchsorted(probe_off, row, side="right").astype(jnp.int32) - 1,
            0, P - 1)
        vid, matched = ref.hash_probe_blocks(bkeys, off_r, probe_keys_part, part)
        bp = jnp.clip(
            jnp.searchsorted(off_r, vid, side="right").astype(jnp.int32) - 1,
            0, P - 1)
        slot = jnp.clip(vid - jnp.take(off_r, bp), 0, cap_r - 1)
        cols = []
        for side, j in col_sides:
            if side == "build":
                val = jnp.take(bvals[:, j, :].reshape(-1), bp * cap_r + slot)
            else:
                val = pv_part[j].astype(jnp.float32)
            cols.append(jnp.where(matched, val, 0.0))
        gke = jnp.where(matched, gk_part, KEY_SENTINEL)
        keys_o, sums_o, counts_o, found = _combine_group_partials(
            gke, cols, matched.astype(jnp.int32), num_groups, gk_part.dtype)
        return keys_o, sums_o[:0] if count_only else sums_o, counts_o, found

    if impl == "xla":
        return xla_arm()

    def pallas_arm():
        cap_s = cap_r
        max_blocks = ceil_div(n, cap_s) + P
        pk, part, src_idx = layout_probe_blocks(
            probe_keys_part, probe_off, probe_sz, cap_s, max_blocks)
        safe = jnp.clip(src_idx, 0, n - 1)
        pad = src_idx >= 0
        gkb = jnp.where(pad, jnp.take(gk_part, safe), KEY_SENTINEL)
        # (B, Cp, capS): every probe value column laid out with the same block map
        pvb = jnp.where(pad[:, None, :],
                        jnp.take(pv_part.astype(jnp.float32), safe, axis=1
                                 ).transpose(1, 0, 2), 0.0)
        pkeys, psums, pcounts = probe_agg_pallas(
            bkeys, bvals, pk, gkb, pvb, part,
            col_sides=tuple(col_sides), interpret=None)
        C = len(col_sides)
        keys_o, sums_o, counts_o, found = _combine_group_partials(
            pkeys.reshape(-1),
            [psums[:, c, :].reshape(-1) for c in range(C)],
            pcounts.reshape(-1), num_groups, gk_part.dtype)
        return keys_o, sums_o[:0] if count_only else sums_o, counts_o, found

    return _pallas_arm("groupjoin_probe_agg", pallas_arm, xla_arm)


# ---------------------------------------------------------------------------
# clustered gather
# ---------------------------------------------------------------------------
def clustered_gather(
    src: jax.Array,
    idx: jax.Array,
    impl: str = "auto",
    *,
    window_rows: int = 2048,
    tile: int = 1024,
):
    """GATHER through a clustered map: out[i] = src[idx[i]], 0 where idx < 0
    or idx >= len(src) (`primitives.gather`'s fill=0 contract).

    impl='auto' runs the windowed kernel where the backend compiles Pallas
    and XLA's take under interpret mode; 'pallas' forces the kernel, 'xla'
    the take. The kernel moves the 32-bit words of a 1-D source; any other
    source takes XLA's path. The kernel is exact for any index, and its
    cost follows each output tile's span (`gather.window_plan`, computed on
    the device): one window for the clustered maps GFTR makes, so the
    choice needs no host sync and no XLA branch beside the kernel."""
    if impl not in ("auto", "pallas", "xla"):
        raise ValueError(f"unknown clustered gather impl {impl!r}")
    n_src = src.shape[0]

    def xla_arm():
        out = jnp.take(src, jnp.clip(idx, 0, n_src - 1), axis=0)
        valid = (idx >= 0) & (idx < n_src)
        return jnp.where(valid.reshape(valid.shape + (1,) * (out.ndim - 1)),
                         out, jnp.zeros_like(out))

    if (impl == "xla" or (impl == "auto" and default_interpret())
            or src.ndim != 1 or src.dtype.itemsize != 4):
        return xla_arm()
    return _pallas_arm(
        "clustered_gather",
        lambda: gather_windowed_pallas(src, idx, window_rows=window_rows,
                                       tile=tile, interpret=None),
        xla_arm)


# ---------------------------------------------------------------------------
# grouped aggregation over sorted keys
# ---------------------------------------------------------------------------
def groupby_sorted_sum(
    sorted_keys: jax.Array,
    values: jax.Array,
    num_groups: int,
    impl: str = "pallas",
    *,
    tile: int = 256,
):
    """Group sums over key-sorted rows: Pallas tile partials + host combine.
    Returns (group_keys, group_sums, count)."""
    if impl == "pallas":
        pk, ps, pc = _pallas_arm(
            "groupby_sorted_sum",
            lambda: segsum_partials_pallas(sorted_keys, values, tile=tile,
                                           interpret=None),
            lambda: ref.segsum_partials(sorted_keys, values, tile))
    else:
        pk, ps, pc = ref.segsum_partials(sorted_keys, values, tile)
    # combine partials: they are key-sorted except sentinel slots; re-sort.
    sk, ss = jax.lax.sort((pk, ps), num_keys=1, is_stable=True)
    valid = sk != KEY_SENTINEL
    bnd = jnp.concatenate([jnp.ones((1,), bool), sk[1:] != sk[:-1]]) & valid
    gid = jnp.cumsum(bnd.astype(jnp.int32)) - 1
    n_found = gid[-1] + 1
    gid = jnp.where(valid & (gid < num_groups), gid, num_groups)
    keys_o = jnp.full((num_groups + 1,), KEY_SENTINEL, sorted_keys.dtype).at[gid].set(
        jnp.where(valid, sk, KEY_SENTINEL), mode="drop"
    )
    sums_o = jax.ops.segment_sum(jnp.where(valid, ss, 0.0), gid, num_segments=num_groups + 1)
    return keys_o[:num_groups], sums_o[:num_groups], jnp.minimum(n_found, num_groups)
