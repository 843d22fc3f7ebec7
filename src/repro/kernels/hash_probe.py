"""Co-partition hash-probe kernel (PHJ match finding, §3.2/§4.3).

The paper's thread block loads one build-side bucket into shared memory and
streams probe keys against it. TPU mapping (DESIGN.md §2):

  shared-memory bucket  ->  (1, capR) build block held in VMEM
  probe stream          ->  (1, capS) probe sub-block (the paper's probe-side
                            sub-partition decomposition, which is also its
                            load-balancing step)
  SIMT probe loop       ->  one (capR x capS) vectorized equality: the build
                            block transposed to a column against the probe
                            row, reduced over sublanes back to a row

Blocks travel as (count, 1, width) arrays so every block's trailing (1,
width) dims equal the array's (the TPU tiling rule).

Probe rows are laid out partition-major and padded so every sub-block is
capS-aligned and belongs to exactly one partition; a scalar-prefetched array
maps sub-block -> partition id, which drives the build BlockSpec. The build
partition offset (for virtual-ID construction) rides along in SMEM.
"""
from __future__ import annotations

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
import jax.numpy as jnp

from .common import resolve_interpret

KEY_SENTINEL = -1


def _first_hit(bk_row, pk_row):
    """(capR, capS) equality of the build block (as a column) against the
    probe row; returns the first matching build slot per probe row (capR
    where none) as a (1, capS) row, and the position iota."""
    eq = (bk_row.T == pk_row) & (pk_row != KEY_SENTINEL)
    iota = jax.lax.broadcasted_iota(jnp.int32, eq.shape, 0)
    return jnp.where(eq, iota, eq.shape[0]).min(axis=0, keepdims=True), iota


def _probe_kernel(part_ref, off_ref, probe_ref, bkeys_ref, vid_ref, hit_ref):
    i = pl.program_id(0)
    bk = bkeys_ref[...]  # (1, capR)
    hitpos, _ = _first_hit(bk, probe_ref[...])
    matched = hitpos < bk.shape[1]
    base = off_ref[part_ref[i]]
    vid_ref[...] = jnp.where(matched, base + hitpos, -1)
    hit_ref[...] = matched.astype(jnp.int32)


def hash_probe_pallas(
    bkeys: jax.Array,  # (P, capR) padded build blocks, KEY_SENTINEL fill
    off_r: jax.Array,  # (P,) partition offsets in the partitioned build array
    probe_blocks: jax.Array,  # (B, capS) partition-major padded probe keys
    block_part: jax.Array,  # (B,) partition id per probe sub-block
    *,
    interpret: bool | None = None,
):
    """Returns (vid, matched): (B, capS) int32 match position in the
    partitioned build array (or -1) and 0/1 hit flags."""
    B, capS = probe_blocks.shape
    P, capR = bkeys.shape
    probe_row = pl.BlockSpec((None, 1, capS), lambda i, part, off: (i, 0, 0))
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            probe_row,
            pl.BlockSpec((None, 1, capR), lambda i, part, off: (part[i], 0, 0)),
        ],
        out_specs=[probe_row, probe_row],
    )
    vid, hit = pl.pallas_call(
        _probe_kernel,
        name="hash_probe",
        grid_spec=spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, 1, capS), jnp.int32),
            jax.ShapeDtypeStruct((B, 1, capS), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(block_part.astype(jnp.int32), off_r.astype(jnp.int32),
      probe_blocks.reshape(B, 1, capS), bkeys.reshape(P, 1, capR))
    return vid.reshape(B, capS), hit.reshape(B, capS)


# ---------------------------------------------------------------------------
# Fused probe + accumulate (group-join): the joined row never leaves VMEM
# ---------------------------------------------------------------------------
def _probe_agg_kernel(part_ref, probe_ref, gk_ref, pv_ref, bkeys_ref,
                      bvals_ref, pk_ref, ps_ref, pc_ref, *, col_sides):
    """One probe sub-block: match finding (vectorized equality against the
    co-partition's build block) immediately followed by tile-local grouped
    aggregation as one-hot matmuls — the §2 scatter-free mapping.

    Instead of writing (vid, hit) per row for a later materialization pass,
    the kernel reduces the tile to at most one (group key, partial sums,
    partial count) tuple per distinct group: the fused analogue of the
    GPU's shared-memory hash-table accumulator. Group assignment needs no
    sort — each row's slot is the first row in the tile carrying the same
    group key (a (capS x capS) equality + iota-min), and the one-hot of
    those slots drives the reduction matmuls.

    `col_sides` (static) maps each output column to its value source:
    ("probe", j) reads row j of pv_ref; ("build", j) fetches the matched
    build value from row j of bvals_ref via a masked sum at the hit
    positions.
    Match finding and group assignment run ONCE per tile no matter how many
    aggregate columns ride the pass."""
    del part_ref  # consumed by the BlockSpec index maps only
    pk = probe_ref[...]  # (1, capS) probe join keys
    gk = gk_ref[...]  # (1, capS) probe group keys
    cap_s = pk.shape[1]
    hitpos, iota_r = _first_hit(bkeys_ref[...], pk)
    matched = hitpos < iota_r.shape[0]
    # one-hot of the (unique, deterministic) first hit position: fetches any
    # number of build value columns without leaving VMEM
    at_hit = iota_r == hitpos  # (capR, capS)
    gke = jnp.where(matched, gk, KEY_SENTINEL)
    # slot of row i = first row in the tile with the same group key
    eqg = (gke.T == gke) & (gke != KEY_SENTINEL)  # (capS, capS), symmetric
    iota_s = jax.lax.broadcasted_iota(jnp.int32, (cap_s, cap_s), 0)
    rep = jnp.where(eqg, iota_s, cap_s).min(axis=0, keepdims=True)  # (1, capS)
    slots = jax.lax.broadcasted_iota(jnp.int32, (cap_s, cap_s), 1)
    oh = (rep.T == slots).astype(jnp.float32)  # (rows, slots)
    for c, (side, j) in enumerate(col_sides):
        if side == "build":
            bcol = bvals_ref[j:j + 1, :].T  # (capR, 1)
            val = jnp.where(at_hit, bcol, 0.0).sum(axis=0, keepdims=True)
        else:
            val = pv_ref[j:j + 1, :]
        ps_ref[c:c + 1, :] = jnp.dot(jnp.where(matched, val, 0.0), oh,
                                     precision=jax.lax.Precision.HIGHEST)
    counts = jnp.dot(matched.astype(jnp.float32), oh,
                     precision=jax.lax.Precision.HIGHEST)
    pc_ref[...] = counts.astype(jnp.int32)
    # slot j only ever receives rows whose group key equals gke[j]
    pk_ref[...] = jnp.where(counts > 0, gke, KEY_SENTINEL)


def probe_agg_pallas(
    bkeys: jax.Array,  # (P, capR) padded build key blocks
    bvals: jax.Array,  # (P, Cb, capR) float32 build value blocks
    probe_blocks: jax.Array,  # (B, capS) partition-major padded probe keys
    gk_blocks: jax.Array,  # (B, capS) probe group keys (KEY_SENTINEL padding)
    pv_blocks: jax.Array,  # (B, Cp, capS) float32 probe value columns
    block_part: jax.Array,  # (B,) partition id per probe sub-block
    *,
    col_sides: tuple,  # static ("probe"|"build", within-side index) per output
    interpret: bool | None = None,
):
    """Fused probe+accumulate partials over any number of aggregate value
    columns in ONE probe pass. Returns (pkeys (B, capS), psums (B, C, capS),
    pcounts (B, capS)): at most one live slot per distinct group per tile
    (KEY_SENTINEL elsewhere); combine with a sorted segmented reduction."""
    import functools

    B, capS = probe_blocks.shape
    P, capR = bkeys.shape
    Cp = pv_blocks.shape[1]
    Cb = bvals.shape[1]
    C = len(col_sides)
    def probe_side(rows):
        return pl.BlockSpec((None, rows, capS), lambda i, part: (i, 0, 0))

    def build_side(rows):
        return pl.BlockSpec((None, rows, capR), lambda i, part: (part[i], 0, 0))

    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B,),
        in_specs=[probe_side(1), probe_side(1), probe_side(Cp),
                  build_side(1), build_side(Cb)],
        out_specs=[probe_side(1), probe_side(C), probe_side(1)],
    )
    pk, ps, pc = pl.pallas_call(
        functools.partial(_probe_agg_kernel, col_sides=tuple(col_sides)),
        name="probe_agg",
        grid_spec=spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, 1, capS), gk_blocks.dtype),
            jax.ShapeDtypeStruct((B, C, capS), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, capS), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(block_part.astype(jnp.int32), probe_blocks.reshape(B, 1, capS),
      gk_blocks.reshape(B, 1, capS), pv_blocks.astype(jnp.float32),
      bkeys.reshape(P, 1, capR), bvals.astype(jnp.float32))
    return pk.reshape(B, capS), ps, pc.reshape(B, capS)


def layout_probe_blocks(
    keys_part: jax.Array,  # partitioned probe keys (contiguous partitions)
    off: jax.Array,
    sz: jax.Array,
    cap_s: int,
    max_blocks: int,
):
    """Decompose partitions into capS-aligned sub-blocks (paper's probe-side
    sub-partitioning). Static worst case: n/capS + P blocks.

    Returns (probe_blocks (B, capS), block_part (B,), src_idx (B, capS)) where
    src_idx maps each slot back to its position in keys_part (-1 = padding).
    """
    P = off.shape[0]
    n = keys_part.shape[0]
    blocks_per = -(-sz // cap_s)  # ceil
    boff = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(blocks_per).astype(jnp.int32)])
    b = jnp.arange(max_blocks, dtype=jnp.int32)
    part = jnp.clip(jnp.searchsorted(boff, b, side="right").astype(jnp.int32) - 1, 0, P - 1)
    sub = b - boff[part]
    valid_block = b < boff[-1]
    j = jnp.arange(cap_s, dtype=jnp.int32)[None, :]
    src = off[part][:, None].astype(jnp.int32) + sub[:, None] * cap_s + j
    in_part = (sub[:, None] * cap_s + j) < sz[part][:, None]
    src_idx = jnp.where(valid_block[:, None] & in_part, src, -1)
    pk = jnp.where(
        src_idx >= 0,
        jnp.take(keys_part, jnp.clip(src_idx, 0, n - 1)),
        KEY_SENTINEL,
    )
    return pk, part, src_idx
