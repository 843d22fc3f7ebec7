"""Pipeline parallelism over a mesh axis (DESIGN.md §6).

GPipe-style schedule: the layer stack is split into S contiguous stages,
one per device along the pipeline mesh axis; the batch is split into M
microbatches that stream through the stages, with activations handed to the
next stage by collective-permute each tick. Total ticks = M + S - 1; bubble
fraction = (S-1)/(M+S-1).

Forward-only (the serving/inference pipeline). The stage function is
user-supplied so the same scheduler runs toy stacks (tests) and full
transformer blocks.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec


def split_layers_to_stages(ws, n_stages: int):
    """Split stacked per-layer weights (pytree with a leading (L, ...) layer
    dim on every leaf) into `n_stages` contiguous stages: (S, L//S, ...).
    The stage count must divide the layer count evenly — stages must be
    load-balanced or the pipeline ticks at the slowest stage's rate."""

    def _split(w):
        L = w.shape[0]
        if L % n_stages:
            raise ValueError(f"{L} layers do not split into {n_stages} stages")
        return w.reshape((n_stages, L // n_stages) + w.shape[1:])

    return jax.tree_util.tree_map(_split, ws)


def _sequential(stage_fn, stages, x):
    """Reference schedule: every stage on the full batch, in order."""
    n_stages = jax.tree_util.tree_leaves(stages)[0].shape[0]
    for s in range(n_stages):
        x = stage_fn(jax.tree_util.tree_map(lambda w: w[s], stages), x)
    return x


def pipeline_forward(stage_fn, stages, x, *, mesh=None, axis=None,
                     n_micro: int = 1):
    """Run `stage_fn(stage_weights, x_micro)` as a pipeline.

    stages: pytree with leading (S, ...) stage dim (split_layers_to_stages).
    x:      (B, ...) batch; B must divide into n_micro microbatches.
    mesh/axis: the mesh axis hosting the stages. S must equal the axis size;
    otherwise (or with no mesh) the sequential reference schedule runs —
    same math, no parallelism — so callers need no topology case-split.

    Matches the sequential schedule exactly up to f32 reassociation
    (asserted to 1e-5 in tests/test_distributed.py).
    """
    n_stages = jax.tree_util.tree_leaves(stages)[0].shape[0]
    if mesh is None or axis is None or dict(mesh.shape).get(axis, 1) != n_stages:
        return _sequential(stage_fn, stages, x)

    B = x.shape[0]
    if B % n_micro:
        raise ValueError(f"batch {B} does not split into {n_micro} microbatches")
    mb = B // n_micro
    xm = x.reshape((n_micro, mb) + x.shape[1:])
    fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def run(stage_ws, xm_loc):
        # stage_ws leaves arrive as (1, L//S, ...) — this device's stage.
        ws = jax.tree_util.tree_map(lambda w: w[0], stage_ws)
        stage = jax.lax.axis_index(axis)
        state = jnp.zeros_like(xm_loc[0])          # activation in flight
        out = jnp.zeros_like(xm_loc)               # valid on the last stage
        for t in range(n_micro + n_stages - 1):
            # stage s works on microbatch t-s this tick; stage 0 pulls fresh
            # input, later stages consume the permuted activation. Ticks
            # outside [0, n_micro) compute garbage that is never stored.
            inp = jnp.where(stage == 0, xm_loc[min(t, n_micro - 1)], state)
            y = stage_fn(ws, inp)
            m = t - (n_stages - 1)
            if 0 <= m < n_micro:
                out = out.at[m].set(jnp.where(stage == n_stages - 1, y, out[m]))
            state = jax.lax.ppermute(y, axis, perm=fwd)
        # replicate the last stage's outputs to every device
        return jax.lax.psum(jnp.where(stage == n_stages - 1, out, 0.0), axis)

    spec_stage = jax.tree_util.tree_map(lambda _: PartitionSpec(axis), stages)
    fn = jax.shard_map(run, mesh=mesh,
                       in_specs=(spec_stage, PartitionSpec()),
                       out_specs=PartitionSpec(),
                       check_vma=False)
    y = fn(stages, xm)
    return y.reshape((B,) + y.shape[2:])
