"""Sort-merge join: SMJ-UM (GFUR pattern, §3.1) and SMJ-OM (GFTR, §4.2).

Phases (paper §2.2):
  transformation  – sort (key, payload/ID) pairs (SORT-PAIRS primitive)
  match finding   – merge join over sorted keys. Merge Path's job on the GPU
                    is per-thread load balance; on TPU the equivalent is a
                    vectorized lower-bound search (one sweep for PK-FK, two
                    for m:n — exactly the paper's single/double Merge Path
                    application, §3.1), tiled in the Pallas kernel.
  materialization – GATHER payload columns. GFUR gathers from the *original*
                    relations with permuted physical IDs (unclustered); GFTR
                    gathers from the *sorted* relations with monotone virtual
                    IDs (clustered) — Algorithm 1 of the paper.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import primitives as prim
from .table import KEY_SENTINEL, Table


# ---------------------------------------------------------------------------
# Match finding over sorted key columns
# ---------------------------------------------------------------------------
def merge_find_pk_fk(kr_sorted: jax.Array, ks_sorted: jax.Array):
    """PK-FK merge: one lower-bound sweep (paper §3.1: 'we only need to apply
    the Merge Path algorithm once').

    Returns (vid_r, matched): for each S' row j, the position of its match in
    R' (virtual ID) and whether it matched. Both outputs are monotone in j,
    so downstream IDs stay clustered — the property GFTR needs (§4.1).
    """
    n_r = kr_sorted.shape[0]
    lb = jnp.searchsorted(kr_sorted, ks_sorted, side="left").astype(jnp.int32)
    lb_c = jnp.minimum(lb, n_r - 1)
    matched = (jnp.take(kr_sorted, lb_c) == ks_sorted) & (lb < n_r)
    matched &= ks_sorted != KEY_SENTINEL
    return lb_c, matched


def merge_find_mn(kr_sorted: jax.Array, ks_sorted: jax.Array, capacity: int):
    """General m:n merge: lower+upper bound sweeps (the paper's two Merge
    Path applications) + expansion.

    Returns (vid_r, vid_s, valid, total) of length `capacity`.
    """
    lb = jnp.searchsorted(kr_sorted, ks_sorted, side="left").astype(jnp.int32)
    ub = jnp.searchsorted(kr_sorted, ks_sorted, side="right").astype(jnp.int32)
    counts = jnp.where(ks_sorted == KEY_SENTINEL, 0, ub - lb)
    row, rank, valid, total = prim.expand_offsets(counts, capacity)
    vid_s = row
    vid_r = jnp.take(lb, row) + rank
    return vid_r, vid_s, valid, total


# ---------------------------------------------------------------------------
# Join drivers
# ---------------------------------------------------------------------------
def _split_payloads(t: Table, key: str):
    return [n for n in t.column_names if n != key]


def smj_join(
    R: Table,
    S: Table,
    *,
    key: str = "k",
    pattern: str = "gftr",  # "gftr" (SMJ-OM) | "gfur" (SMJ-UM)
    out_size: int | None = None,
    mode: str = "pk_fk",  # "pk_fk" | "mn"
    find_impl: str = "xla",  # "xla" | "pallas" (windowed lower-bound kernel)
):
    """End-to-end sort-merge join. Returns (Table, valid_count).

    Output columns: key + R payloads + S payloads; rows >= valid_count are
    padding (key == KEY_SENTINEL).
    """
    if out_size is None:
        out_size = S.num_rows if mode == "pk_fk" else S.num_rows * 2
    r_pay, s_pay = _split_payloads(R, key), _split_payloads(S, key)

    if pattern == "gfur":
        return _smj_gfur(R, S, key, r_pay, s_pay, out_size, mode, find_impl)
    if pattern == "gftr":
        return _smj_gftr(R, S, key, r_pay, s_pay, out_size, mode, find_impl)
    raise ValueError(f"unknown pattern {pattern!r}")


def _find(kr, ks, mode, out_size, find_impl="xla"):
    """Shared match-find + compaction producing clustered (vid_r, vid_s)."""
    if mode == "pk_fk":
        if find_impl == "pallas":
            from repro.kernels import ops as _kops

            n_r = kr.shape[0]
            lb = _kops.merge_lower_bound(kr, ks, "auto")
            lb_c = jnp.minimum(lb, n_r - 1)
            matched = (jnp.take(kr, lb_c) == ks) & (lb < n_r) & (ks != KEY_SENTINEL)
            vid_r = lb_c
        else:
            vid_r, matched = merge_find_pk_fk(kr, ks)
        vid_s = jnp.arange(ks.shape[0], dtype=jnp.int32)
        (keys_o, vr_o, vs_o), count = prim.compact(
            matched, [ks, vid_r, vid_s], out_size, fill=KEY_SENTINEL
        )
        valid = jnp.arange(out_size) < count
        return keys_o, vr_o, vs_o, valid, count
    vid_r, vid_s, valid, total = merge_find_mn(kr, ks, out_size)
    keys_o = jnp.where(valid, jnp.take(ks, vid_s), KEY_SENTINEL)
    return keys_o, vid_r, vid_s, valid, jnp.minimum(total, out_size)


def _smj_gfur(R, S, key, r_pay, s_pay, out_size, mode, find_impl="xla"):
    # Transformation: sort only (key, physical ID) — the "narrow" transform.
    with prim.phase("partition"):
        id_r = jnp.arange(R.num_rows, dtype=jnp.int32)
        id_s = jnp.arange(S.num_rows, dtype=jnp.int32)
        kr, pid_r = prim.sort_pairs(R[key], id_r)
        ks, pid_s = prim.sort_pairs(S[key], id_s)
    # Match finding (virtual ids w.r.t. sorted arrays) ...
    with prim.phase("probe"):
        keys_o, vr, vs, valid, count = _find(kr, ks, mode, out_size, find_impl)
    # ... translated to *physical* IDs of the untransformed relations: the
    # permutation makes them unclustered — this is GFUR's flaw (§3.3).
    with prim.phase("materialize"):
        ID_R = jnp.where(valid, jnp.take(pid_r, vr), -1)
        ID_S = jnp.where(valid, jnp.take(pid_s, vs), -1)
        cols = {key: keys_o}
        for n in r_pay:  # unclustered gathers from original R
            cols[n] = prim.gather(R[n], ID_R, fill=0)
        for n in s_pay:  # unclustered gathers from original S
            cols[n] = prim.gather(S[n], ID_S, fill=0)
    return Table(cols), count


def _smj_gftr(R, S, key, r_pay, s_pay, out_size, mode, find_impl="xla"):
    # Algorithm 1 with the one-permutation refinement (DESIGN.md §8): the
    # key sort is planned ONCE per relation, and every payload column —
    # first or lazy — is transformed with a single apply_permutation gather.
    with prim.phase("partition"):
        kr, perm_r = prim.plan_sort_permutation(R[key])
        ks, perm_s = prim.plan_sort_permutation(S[key])
    with prim.phase("materialize"):
        tr = {n: prim.apply_permutation(perm_r, R[n]) for n in r_pay[:1]}
        ts = {n: prim.apply_permutation(perm_s, S[n]) for n in s_pay[:1]}
    transform_r = lambda n: prim.apply_permutation(perm_r, R[n])
    transform_s = lambda n: prim.apply_permutation(perm_s, S[n])

    # Match finding on sorted keys with *virtual* tuple IDs (line 3).
    with prim.phase("probe"):
        keys_o, vid_r, vid_s, valid, count = _find(kr, ks, mode, out_size,
                                                   find_impl)

    # Materialization phase (lines 4-9): clustered gathers from transformed
    # relations, transforming remaining payload columns one at a time.
    with prim.phase("materialize"):
        ID_R = jnp.where(valid, vid_r, -1)
        ID_S = jnp.where(valid, vid_s, -1)
        cols = {key: keys_o}
        for i, n in enumerate(r_pay):
            src = tr[n] if i == 0 else transform_r(n)
            cols[n] = prim.clustered_gather(src, ID_R)
        for i, n in enumerate(s_pay):
            src = ts[n] if i == 0 else transform_s(n)
            cols[n] = prim.clustered_gather(src, ID_S)
    return Table(cols), count
