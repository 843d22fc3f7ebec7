"""Clustered GATHER kernel (materialization phase, §2.3 / Table 4).

The paper's unclustered GATHER loads ~4.5 cache lines per warp instruction;
clustered maps load ~1.5. On TPU the analogue is the HBM->VMEM window: for a
clustered gather map (GFTR's second gather: a monotone probe-side ID, or a
build-side ID that stays within a few co-partitions per output tile), the
valid indices of an output tile span a small input window, so the kernel
streams that window into VMEM per tile and resolves the gather *inside*
VMEM as a masked reduction over the window (a one-hot selection of the
source's 32-bit words, exact for every 32-bit dtype).

Geometry: the source is viewed as (n_blocks, 1, block_rows) blocks and each
tile of `tile` outputs sees WINDOW_BLOCKS consecutive blocks (`window_rows`
= WINDOW_BLOCKS * block_rows source rows of work per output), starting at
the block of the tile's smallest valid index. Any tile whose valid indices
span at most window_rows - block_rows + 1 rows fits one window, wherever it
starts. When the source length is a multiple of block_rows (and at least
one window) and the output length a multiple of the tile — the padded
buckets and the join capacities of the served path — the blocked views are
reshapes alone; otherwise they are padded copies.

`window_plan` measures every tile's span on the device. The first window
comes through the kernel's pipeline; a tile whose span is wider fetches the
next windows itself (one DMA each), so the kernel is exact for any index
and costs one window per window_rows of span: one for GFTR's maps, whose
tiles' spans also tile the source, so the extra windows of a sparse map sum
to about n_src / window_rows. A tile with no valid index (window -1) is
skipped. No host sync and no XLA gather beside the kernel: an XLA branch
would hold index-length buffers in the compiled program whether or not it
ran.
"""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
import jax.numpy as jnp

from .common import ceil_div, resolve_interpret

# window rows resolved per masked reduction: bounds the (chunk, tile)
# selection tile that lives in VMEM at once
WINDOW_CHUNK = 256
# source blocks per tile window: a finer alignment than two blocks of the
# window's half lets a span start anywhere in its first block and still fit
WINDOW_BLOCKS = 4


def _tiles(idx: jax.Array, tile: int) -> jax.Array:
    """idx as (n_tiles, 1, tile), padded with -1: the kernel's view, a
    reshape alone where the tile divides the length."""
    n_tiles = ceil_div(idx.shape[0], tile)
    idx = idx.astype(jnp.int32)
    if n_tiles * tile != idx.shape[0]:
        idx = jnp.pad(idx, (0, n_tiles * tile - idx.shape[0]),
                      constant_values=-1)
    return idx.reshape(n_tiles, 1, tile)


def _blocks(src: jax.Array, block_rows: int) -> jax.Array:
    """src as (n_blocks, 1, block_rows) with at least WINDOW_BLOCKS blocks:
    a reshape alone where block_rows divides the length, else padded."""
    n_blocks = max(ceil_div(src.shape[0], block_rows), WINDOW_BLOCKS)
    if n_blocks * block_rows != src.shape[0]:
        src = jnp.pad(src, (0, n_blocks * block_rows - src.shape[0]))
    return src.reshape(n_blocks, 1, block_rows)


def window_plan(idx: jax.Array, n_src: int, *, window_rows: int = 2048,
                tile: int = 1024):
    """(win, n_win) for gathering `idx` from a source of `n_src` rows, both
    int32 per tile of `tile` outputs, computed on the device.

    win[t] is the tile's first source block (units of window_rows //
    WINDOW_BLOCKS): the block of its smallest valid index (0 <= idx <
    n_src), clipped so a window stays inside the source; -1 for a tile
    with no valid index. n_win[t] is the number of consecutive windows of
    window_rows rows, from that block on, that reach its largest valid
    index: 1 where the tile's span fits one window, 0 for a tile with no
    valid index."""
    block_rows = window_rows // WINDOW_BLOCKS
    n_blocks = max(ceil_div(n_src, block_rows), WINDOW_BLOCKS)
    # reduce lane rows of 128 first: a (rows, 128) view of a 1-D array is
    # a reshape alone on TPU, where a (n_tiles, tile) one is a relayout copy
    idx = _tiles(idx, tile).reshape(-1, 128)
    valid = (idx >= 0) & (idx < n_src)
    lo = jnp.min(jnp.where(valid, idx, n_src), axis=1)
    hi = jnp.max(jnp.where(valid, idx, -1), axis=1)
    lo = lo.reshape(-1, tile // 128).min(axis=1)
    hi = hi.reshape(-1, tile // 128).max(axis=1)
    live = hi >= 0
    win = jnp.minimum(lo // block_rows, n_blocks - WINDOW_BLOCKS)
    n_win = (hi // block_rows - win + WINDOW_BLOCKS) // WINDOW_BLOCKS
    return (jnp.where(live, win, -1).astype(jnp.int32),
            jnp.where(live, n_win, 0).astype(jnp.int32))


def _select(blocks, idx, base: jax.Array, block_rows: int, out):
    """out + the words of `blocks` (consecutive source blocks from block
    `base`) at the tile's indices: a masked sum over the window, so an
    index outside it adds 0."""
    rel = idx - base * block_rows
    chunk = min(WINDOW_CHUNK, block_rows)
    pos = jax.lax.broadcasted_iota(jnp.int32, (chunk, rel.shape[1]), 0)
    for b, block in enumerate(blocks):
        col = block.T  # (block_rows, 1)
        for c in range(0, block_rows, chunk):
            hit = pos == rel - (b * block_rows + c)
            out = out + jnp.where(hit, col[c:c + chunk], 0).sum(
                axis=0, keepdims=True)
    return out


def _gather_kernel(block_rows: int, n_blocks: int, w_ref, n_ref, idx_ref,
                   *refs):
    *blocks, src_hbm, out_ref, extra, sem = refs
    i = pl.program_id(0)
    w = w_ref[i]

    @pl.when(w < 0)
    def _():  # no valid index in this tile
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    @pl.when(w >= 0)
    def _():
        idx = idx_ref[...]  # (1, T)
        # the first window arrives through the pipeline
        out = _select([b[...] for b in blocks], idx, w, block_rows,
                      jnp.zeros(idx.shape, jnp.int32))

        def window(k, out):
            # a span wider than one window: fetch the next one, clipped to
            # the source, and count only indices it newly covers
            start = w + k * WINDOW_BLOCKS
            base = jnp.minimum(start, n_blocks - WINDOW_BLOCKS)
            copy = pltpu.make_async_copy(
                src_hbm.at[pl.ds(base, WINDOW_BLOCKS)], extra, sem)
            copy.start()
            copy.wait()
            new = jnp.where(idx >= start * block_rows, idx, -1)
            return _select([extra[j] for j in range(WINDOW_BLOCKS)], new,
                           base, block_rows, out)

        out_ref[...] = jax.lax.fori_loop(1, n_ref[i], window, out)


def gather_windowed_pallas(
    src: jax.Array,
    idx: jax.Array,
    *,
    window_rows: int = 2048,
    tile: int = 1024,
    interpret: bool | None = None,
) -> jax.Array:
    """out[i] = src[idx[i]]; 0 where idx < 0 or idx >= len(src). Exact for
    any idx; fast where each tile's indices span few windows (clustered
    maps), since a tile costs one window per window_rows of its span.
    `tile` is a multiple of 128."""
    if src.ndim != 1 or src.dtype.itemsize != 4:
        raise ValueError(f"the kernel gathers 32-bit words, not {src.dtype}{src.shape}")
    n_src, n_out = src.shape[0], idx.shape[0]
    block_rows = window_rows // WINDOW_BLOCKS
    win, n_win = window_plan(idx, n_src, window_rows=window_rows, tile=tile)
    # the selection sums integer words: exact for every 32-bit dtype's bits
    src3 = _blocks(jax.lax.bitcast_convert_type(src, jnp.int32), block_rows)
    idx3 = _tiles(idx, tile)
    n_blocks, n_tiles = src3.shape[0], idx3.shape[0]

    def block_spec(j):
        return pl.BlockSpec((None, 1, block_rows),
                            lambda i, w, n: (jnp.maximum(w[i], 0) + j, 0, 0))

    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec((None, 1, tile), lambda i, w, n: (i, 0, 0))]
                 + [block_spec(j) for j in range(WINDOW_BLOCKS)]
                 + [pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((None, 1, tile), lambda i, w, n: (i, 0, 0)),
        scratch_shapes=[pltpu.VMEM((WINDOW_BLOCKS, 1, block_rows), jnp.int32),
                        pltpu.SemaphoreType.DMA],
    )
    out = pl.pallas_call(
        functools.partial(_gather_kernel, block_rows, n_blocks),
        name="clustered_gather",
        grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct((n_tiles, 1, tile), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(win, n_win, idx3, *([src3] * (WINDOW_BLOCKS + 1)))
    return jax.lax.bitcast_convert_type(out.reshape(-1)[:n_out], src.dtype)
