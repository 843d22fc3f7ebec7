"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the semantic ground truth the kernels are validated against
(tests sweep shapes/dtypes and assert_allclose kernel-vs-ref)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

KEY_SENTINEL = -1


def histogram(digits: jax.Array, num_bins: int) -> jax.Array:
    """Counts per digit value. digits int32 in [0, num_bins)."""
    return jnp.bincount(digits, length=num_bins).astype(jnp.int32)


def partition_ranks(digits: jax.Array, num_bins: int) -> jax.Array:
    """Stable-partition destination index per element:
    dest[i] = offset[digit[i]] + |{j < i : digit[j] == digit[i]}|."""
    n = digits.shape[0]
    oh = (digits[:, None] == jnp.arange(num_bins)[None, :]).astype(jnp.int32)
    within = jnp.cumsum(oh, axis=0) - oh  # exclusive rank within digit
    sizes = oh.sum(axis=0)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(sizes)[:-1]])
    return (offsets[digits] + within[jnp.arange(n), digits]).astype(jnp.int32)


def lower_bound(build_sorted: jax.Array, probe: jax.Array) -> jax.Array:
    """searchsorted(build, probe, 'left')."""
    return jnp.searchsorted(build_sorted, probe, side="left").astype(jnp.int32)


def upper_bound(build_sorted: jax.Array, probe: jax.Array) -> jax.Array:
    return jnp.searchsorted(build_sorted, probe, side="right").astype(jnp.int32)


def hash_probe_blocks(bkeys: jax.Array, off_r: jax.Array, probe_keys: jax.Array,
                      probe_part: jax.Array):
    """Co-partition PK probe. bkeys (P, capR) padded build blocks (sentinel
    fill); probe row j belongs to partition probe_part[j]. Returns
    (vid_r, matched): position of the unique match in the partitioned build
    array, else (-1ish, False)."""
    cand = jnp.take(bkeys, probe_part, axis=0)  # (n, capR)
    eq = (cand == probe_keys[:, None]) & (probe_keys[:, None] != KEY_SENTINEL)
    hit = jnp.argmax(eq, axis=1).astype(jnp.int32)
    matched = jnp.any(eq, axis=1)
    vid = jnp.take(off_r, probe_part).astype(jnp.int32) + hit
    return vid, matched


def windowed_gather(src: jax.Array, idx: jax.Array) -> jax.Array:
    """out[i] = src[idx[i]] (idx assumed in-range)."""
    return jnp.take(src, idx, axis=0)


def segsum_partials(sorted_keys: jax.Array, values: jax.Array, tile: int):
    """Per-tile partial aggregation over key-sorted rows.

    Returns (pkeys, psums, pcounts), each (num_tiles*tile,): slot t*tile+g is
    tile t's local group g (KEY_SENTINEL where no group). Summing partials by
    key reproduces the global group sums."""
    n = sorted_keys.shape[0]
    pad = -n % tile
    k = jnp.concatenate([sorted_keys, jnp.full((pad,), KEY_SENTINEL, sorted_keys.dtype)])
    v = jnp.concatenate([values, jnp.zeros((pad,), values.dtype)])
    k = k.reshape(-1, tile)
    v = v.reshape(-1, tile)
    valid = k != KEY_SENTINEL
    bnd = jnp.concatenate([jnp.ones((k.shape[0], 1), bool), k[:, 1:] != k[:, :-1]], 1) & valid
    lgid = jnp.cumsum(bnd.astype(jnp.int32), axis=1) - 1
    lgid = jnp.where(valid, lgid, tile)
    oh = jax.nn.one_hot(lgid, tile, dtype=jnp.float32)  # (T, tile, tile)
    # a masked sum over rows, as the kernel does: exact f32 adds in row order
    psums = jnp.where(oh > 0, v.astype(jnp.float32)[:, :, None], 0.0).sum(axis=1)
    pcounts = jnp.einsum("tbg->tg", oh)
    T = k.shape[0]
    pkeys = (
        jnp.full((T, tile + 1), KEY_SENTINEL, sorted_keys.dtype)
        .at[jnp.arange(T)[:, None], jnp.where(bnd, lgid, tile)]
        .set(k, mode="drop")[:, :tile]
    )
    return pkeys.reshape(-1), psums.reshape(-1), pcounts.reshape(-1).astype(jnp.int32)
