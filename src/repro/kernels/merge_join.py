"""Merge-join match-finding kernel (SMJ, §3.1) — windowed lower-bound.

GPU Merge Path exists to load-balance threads over the merge frontier; on
TPU the grid is balanced by construction (equal probe tiles), and the
per-tile work becomes a dense rank count against a VMEM-resident window of
the sorted build keys (DESIGN.md §2):

    lb(p) = win_start + |{ w in window : w < p }|

which is exact when the window covers [lb(first), lb(last)] of the tile —
guaranteed by the two-level scheme in ops.py (a cheap coarse searchsorted of
tile boundaries chooses each tile's window; tiles whose span exceeds the
window fall back to XLA searchsorted). Probe tiles are sorted, so windows
are monotone — sequential HBM traffic, the same clustering argument as GFTR.

Layout: build keys padded to (n_wb + 1, 1, W) with an INT_MAX tail block;
each grid step sees two consecutive W-blocks (an aligned 2W window)
selected by a scalar-prefetched window index. The window is compared as a
(W, 1) column against the (1, T) probe row, WINDOW_CHUNK rows at a time.
"""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
import jax.numpy as jnp

from .common import ceil_div, resolve_interpret
from .gather import WINDOW_CHUNK

INT_MAX = jnp.iinfo(jnp.int32).max


def _lb_kernel(window_rows: int, w_ref, probe_ref, lo_ref, hi_ref, out_ref):
    i = pl.program_id(0)
    p = probe_ref[...]  # (1, T)
    below = jnp.zeros(p.shape, jnp.int32)
    for half in (lo_ref, hi_ref):
        col = half[...].T  # (W, 1) sorted
        for c in range(0, window_rows, WINDOW_CHUNK):
            lt = col[c:c + WINDOW_CHUNK] < p
            below = below + lt.astype(jnp.int32).sum(axis=0, keepdims=True)
    out_ref[...] = w_ref[i] * window_rows + below


def lower_bound_windowed_pallas(
    build_sorted: jax.Array,
    probe_sorted: jax.Array,
    win_idx: jax.Array,
    *,
    window_rows: int = 1024,
    tile: int = 1024,
    interpret: bool | None = None,
) -> jax.Array:
    """lb per probe element, given per-tile window indices (in units of
    window_rows). Caller guarantees the 2W window covers each tile's range
    (ops.py checks and falls back otherwise). Returns int32 (n_probe,)."""
    n_b, n_p = build_sorted.shape[0], probe_sorted.shape[0]
    n_wb = ceil_div(n_b, window_rows)
    bpad = jnp.full((n_wb * window_rows - n_b + window_rows,), INT_MAX, build_sorted.dtype)
    build3 = jnp.concatenate([build_sorted, bpad]).reshape(n_wb + 1, 1, window_rows)

    n_tiles = ceil_div(n_p, tile)
    ppad = jnp.full((n_tiles * tile - n_p,), INT_MAX, probe_sorted.dtype)
    probe3 = jnp.concatenate([probe_sorted, ppad]).reshape(n_tiles, 1, tile)

    win_idx = jnp.clip(win_idx.astype(jnp.int32), 0, n_wb - 1)

    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((None, 1, tile), lambda i, w: (i, 0, 0)),
            pl.BlockSpec((None, 1, window_rows), lambda i, w: (w[i], 0, 0)),
            pl.BlockSpec((None, 1, window_rows), lambda i, w: (w[i] + 1, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, 1, tile), lambda i, w: (i, 0, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_lb_kernel, window_rows),
        grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct((n_tiles, 1, tile), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(win_idx, probe3, build3, build3)
    return out.reshape(-1)[:n_p]
