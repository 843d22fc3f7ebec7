"""Clustered GATHER kernel (materialization phase, §2.3 / Table 4).

The paper's unclustered GATHER loads ~4.5 cache lines per warp instruction;
clustered maps load ~1.5. On TPU the analogue is the HBM->VMEM window: for a
clustered gather map, the indices of an output tile span a small input
window, so the kernel streams one aligned 2W window into VMEM per tile and
resolves the gather *inside* VMEM as a masked reduction over the window
(a one-hot selection, exact for every dtype). Unclustered maps have
unbounded spans and fall back to XLA's random-access take (ops.py makes that
dispatch — it is the measurable difference the paper's Figure 7 is about).
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


def _gather_kernel(window_rows: int, w_ref, idx_ref, lo_ref, hi_ref, out_ref):
    i = pl.program_id(0)
    rel = idx_ref[...] - w_ref[i] * window_rows  # (1, T)
    # sum identity that keeps the selected value's bits: -0.0 for floats
    zero = jnp.asarray(
        -0.0 if jnp.issubdtype(out_ref.dtype, jnp.floating) else 0,
        out_ref.dtype)
    out = jnp.full(rel.shape, zero, out_ref.dtype)
    for h, half in enumerate((lo_ref, hi_ref)):
        col = half[...].T  # (W, 1)
        for c in range(0, window_rows, WINDOW_CHUNK):
            part = col[c:c + WINDOW_CHUNK]
            pos = jax.lax.broadcasted_iota(
                jnp.int32, (part.shape[0], rel.shape[1]), 0)
            hit = pos == rel - (h * window_rows + c)
            out = out + jnp.where(hit, part, zero).sum(axis=0, keepdims=True)
    out_ref[...] = out


def gather_windowed_pallas(
    src: jax.Array,
    idx: jax.Array,
    win_idx: jax.Array,
    *,
    window_rows: int = 1024,
    tile: int = 1024,
    interpret: bool | None = None,
) -> jax.Array:
    """out[i] = src[idx[i]] for clustered idx. win_idx gives each tile's
    aligned window (units of window_rows); indices outside a tile's 2W
    window produce 0 (callers pre-check spans; ops.py dispatches)."""
    n_src, n_out = src.shape[0], idx.shape[0]
    n_wb = ceil_div(n_src, window_rows)
    spad = jnp.zeros((n_wb * window_rows - n_src + window_rows,), src.dtype)
    src3 = jnp.concatenate([src, spad]).reshape(n_wb + 1, 1, window_rows)

    n_tiles = ceil_div(n_out, tile)
    ipad = jnp.full((n_tiles * tile - n_out,), -1, jnp.int32)
    idx3 = jnp.concatenate([idx.astype(jnp.int32), ipad]).reshape(n_tiles, 1, tile)
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
        functools.partial(_gather_kernel, window_rows),
        grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct((n_tiles, 1, tile), src.dtype),
        interpret=resolve_interpret(interpret),
    )(win_idx, idx3, src3, src3)
    return out.reshape(-1)[:n_out]
