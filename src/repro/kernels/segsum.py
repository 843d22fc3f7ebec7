"""Segmented partial-aggregation kernel (grouped aggregations,
extension-per-assigned-title; see groupby.py).

Input rows are key-sorted; each grid step processes one VMEM-resident tile,
detects run boundaries, and reduces each local run with one-hot matmuls
(sum/count) — scatter-free MXU work, the TPU analogue of a thread block's
shared-memory hash aggregation. Per-tile partials (at most one per distinct
key per tile) are combined by a cheap host-side pass; heavy-hitter keys
collapse tile-locally first, which is how skew is absorbed.
"""
from __future__ import annotations

import jax
from jax.experimental import pallas as pl
import jax.numpy as jnp

from .common import ceil_div, resolve_interpret

KEY_SENTINEL = -1


def _segsum_kernel(k_ref, v_ref, pk_ref, ps_ref, pc_ref):
    k = k_ref[...]  # (1, T) sorted within tile
    v = v_ref[...].astype(jnp.float32)
    T = k.shape[1]
    valid = k != KEY_SENTINEL
    rows = jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (T, T), 1)
    kc = k.T  # (T, 1)
    # prev[t] = k[t-1] (KEY_SENTINEL at t=0): masked sum over the key column
    prev = jnp.where(rows == cols - 1, kc, 0).sum(axis=0, keepdims=True)
    prev = jnp.where(cols[:1] == 0, KEY_SENTINEL, prev)
    bnd = (k != prev) & valid
    # local group id = inclusive count of run heads - 1, as a 0/1 matmul
    # against the upper triangle (bf16 operands, f32 accumulation: exact)
    heads = jnp.dot(bnd.astype(jnp.bfloat16), (rows <= cols).astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32)
    lgid = jnp.where(valid, heads.astype(jnp.int32) - 1, T)
    oh = lgid.T == cols  # (rows T, groups T)
    # masked sums over rows: the reference's exact expression and order
    ps_ref[...] = jnp.where(oh, v.T, 0.0).sum(axis=0, keepdims=True)
    counts = oh.astype(jnp.int32).sum(axis=0, keepdims=True)
    pc_ref[...] = counts
    # group keys via run-head selection (one head per group column)
    head = oh & (bnd.astype(jnp.int32).T > 0)
    pk = jnp.where(head, kc, 0).sum(axis=0, keepdims=True)
    pk_ref[...] = jnp.where(counts > 0, pk, KEY_SENTINEL)


def segsum_partials_pallas(
    sorted_keys: jax.Array,
    values: jax.Array,
    *,
    tile: int = 256,
    interpret: bool | None = None,
):
    """Per-tile (keys, sums, counts) partials over key-sorted input.
    Matches ref.segsum_partials."""
    n = sorted_keys.shape[0]
    n_tiles = ceil_div(n, tile)
    kp = jnp.concatenate(
        [sorted_keys, jnp.full((n_tiles * tile - n,), KEY_SENTINEL, sorted_keys.dtype)]
    ).reshape(n_tiles, 1, tile)
    vp = jnp.concatenate(
        [values, jnp.zeros((n_tiles * tile - n,), values.dtype)]
    ).reshape(n_tiles, 1, tile)
    row = pl.BlockSpec((None, 1, tile), lambda i: (i, 0, 0))
    pk, ps, pc = pl.pallas_call(
        _segsum_kernel,
        grid=(n_tiles,),
        in_specs=[row, row],
        out_specs=[row, row, row],
        out_shape=[
            jax.ShapeDtypeStruct((n_tiles, 1, tile), sorted_keys.dtype),
            jax.ShapeDtypeStruct((n_tiles, 1, tile), jnp.float32),
            jax.ShapeDtypeStruct((n_tiles, 1, tile), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(kp, vp)
    return pk.reshape(-1), ps.reshape(-1), pc.reshape(-1)
