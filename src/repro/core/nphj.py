"""Non-partitioned hash join (cuDF-analogue baseline, paper Fig. 1/8).

A single global open-addressing table: build inserts R's keys directly, probe
streams S's keys against it — random global-memory accesses on both sides,
which is exactly why the paper's partitioned algorithms beat it. We keep it
as the baseline for the Fig. 8/10 benchmarks.

TPU adaptation of atomic insertion: CUDA uses atomicCAS; XLA has no atomics,
so each linear-probing round inserts via a deterministic max-scatter
(`.at[idx].max(rank)`) and losers retry in the next round. With load factor
<= 1/4 and 16 rounds, failures are (checked to be) absent for the workloads
we run; the returned `failed` count makes the fallback explicit.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import primitives as prim
from .hash_join import hash32
from .table import KEY_SENTINEL, Table

_EMPTY = jnp.int32(-1)


def build_table(keys: jax.Array, table_size: int, max_rounds: int = 16):
    """Insert unique keys into an open-addressing table.

    Returns (slot_keys, slot_vids, failed_count)."""
    n = keys.shape[0]
    mask = jnp.uint32(table_size - 1)
    h = (hash32(keys) & mask).astype(jnp.int32)
    rank = jnp.arange(n, dtype=jnp.int32)

    slot_rank = jnp.full((table_size,), _EMPTY, jnp.int32)
    inserted = jnp.zeros((n,), bool)
    slot_of = jnp.full((n,), -1, jnp.int32)

    def round_body(a, state):
        slot_rank, inserted, slot_of = state
        idx = ((h + a) & jnp.int32(table_size - 1)).astype(jnp.int32)
        occupied = jnp.take(slot_rank, idx) != _EMPTY
        want = (~inserted) & (~occupied)
        cand = jnp.where(want, rank, _EMPTY)
        slot_rank = slot_rank.at[jnp.where(want, idx, table_size)].max(cand, mode="drop")
        won = want & (jnp.take(slot_rank, idx) == rank)
        slot_of = jnp.where(won, idx, slot_of)
        inserted = inserted | won
        return slot_rank, inserted, slot_of

    slot_rank, inserted, slot_of = jax.lax.fori_loop(
        0, max_rounds, round_body, (slot_rank, inserted, slot_of)
    )
    slot_keys = jnp.full((table_size,), KEY_SENTINEL, keys.dtype)
    slot_vids = jnp.full((table_size,), -1, jnp.int32)
    safe = jnp.where(inserted, slot_of, table_size)
    slot_keys = slot_keys.at[safe].set(keys, mode="drop")
    slot_vids = slot_vids.at[safe].set(rank, mode="drop")
    failed = jnp.sum(~inserted)
    return slot_keys, slot_vids, failed


def probe_table(slot_keys, slot_vids, probe_keys, max_rounds: int = 16):
    """Probe: returns (vid_r, matched) per probe row (unique build keys)."""
    table_size = slot_keys.shape[0]
    mask = jnp.uint32(table_size - 1)
    h = (hash32(probe_keys) & mask).astype(jnp.int32)
    found_vid = jnp.full(probe_keys.shape, -1, jnp.int32)
    done = probe_keys == KEY_SENTINEL

    def round_body(a, state):
        found_vid, done = state
        idx = ((h + a) & jnp.int32(table_size - 1)).astype(jnp.int32)
        sk = jnp.take(slot_keys, idx)
        hit = (~done) & (sk == probe_keys)
        found_vid = jnp.where(hit, jnp.take(slot_vids, idx), found_vid)
        done = done | hit | (sk == KEY_SENTINEL)  # empty slot terminates chain
        return found_vid, done

    found_vid, _ = jax.lax.fori_loop(0, max_rounds, round_body, (found_vid, done))
    return found_vid, found_vid >= 0


def nphj_join(
    R: Table,
    S: Table,
    *,
    key: str = "k",
    out_size: int | None = None,
    load_factor: float = 0.25,
    max_rounds: int = 16,
):
    """cuDF-style non-partitioned hash join (PK-FK). Returns (Table, count).

    Materialization matches the paper's description: probe side is streamed
    (clustered), build side gathered by hash-permuted vids (unclustered).
    """
    if out_size is None:
        out_size = S.num_rows
    table_size = 1 << max(3, (int(R.num_rows / load_factor) - 1).bit_length())
    with prim.phase("partition"):  # the build side's key structure
        slot_keys, slot_vids, _failed = build_table(R[key], table_size,
                                                    max_rounds)
    with prim.phase("probe"):
        vid_r, matched = probe_table(slot_keys, slot_vids, S[key], max_rounds)
        vid_s = jnp.arange(S.num_rows, dtype=jnp.int32)
        (keys_o, vr, vs), count = prim.compact(
            matched, [S[key], vid_r, vid_s], out_size, fill=KEY_SENTINEL
        )
        valid = jnp.arange(out_size) < count
    with prim.phase("materialize"):
        cols = {key: keys_o}
        for n in R.column_names:
            if n != key:
                cols[n] = prim.gather(R[n], jnp.where(valid, vr, -1), fill=0)
        for n in S.column_names:
            if n != key:
                cols[n] = prim.gather(S[n], jnp.where(valid, vs, -1), fill=0)
    return Table(cols), count
