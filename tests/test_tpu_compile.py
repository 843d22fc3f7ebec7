"""Compile every production Pallas kernel for a described TPU v5e chip.

Interpret mode (the CPU default) accepts kernels that the TPU's Mosaic
compiler refuses: blocks that break the (8, 128) tiling rule, in-kernel
shape casts, VMEM overflow. These tests lower each kernel with
`interpret=False` at a real block size (2^22 rows) for one chip of a
described `v5e:2x2` topology — nothing runs, no chip is needed — and check
that the compiled program carries the kernel (`tpu_custom_call`), under the
stable name its `pallas_call` gives it (the name a device profile shows).

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this file.
"""
from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.gather import gather_windowed_pallas
from repro.kernels.hash_probe import hash_probe_pallas, probe_agg_pallas
from repro.kernels.histogram import histogram_pallas
from repro.kernels.merge_join import lower_bound_windowed_pallas
from repro.kernels.radix_partition import (block_histograms_pallas,
                                           partition_plan_pallas,
                                           partition_ranks_pallas)
from repro.kernels.segsum import segsum_partials_pallas

ROWS = 1 << 22
CAP = 256  # core.hash_join.BUILD_BLOCK: build block / probe sub-block width
PARTS = 1 << 16  # PHJ fan-out at 2^22 build rows (choose_partition_bits)
TILE = 1024  # merge tile and window (kernels.ops defaults)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no description
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from the
    # persistent cache; keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


I32, F32 = jnp.int32, jnp.float32
N_PROBE_BLOCKS = ROWS // CAP + PARTS  # layout_probe_blocks' static worst case


KERNELS = {
    "block_histograms": (
        lambda d: block_histograms_pallas(d, 256, interpret=False),
        [((ROWS,), I32)]),
    "partition_ranks": (
        lambda d: partition_ranks_pallas(d, 257, interpret=False),
        [((ROWS,), I32)]),
    "partition_plan_kernel": (
        lambda d, c: partition_plan_pallas(d, PARTS + 1, carry=(c,),
                                           pass_impl="kernel",
                                           interpret=False),
        [((ROWS,), I32), ((ROWS,), I32)]),
    "histogram": (
        lambda d: histogram_pallas(d, 256, interpret=False),
        [((ROWS,), I32)]),
    "gather_windowed_f32": (
        lambda s, i: gather_windowed_pallas(s, i, interpret=False),
        [((ROWS,), F32), ((ROWS,), I32)]),
    "gather_windowed_i32": (
        lambda s, i: gather_windowed_pallas(s, i, interpret=False),
        [((ROWS,), I32), ((ROWS,), I32)]),
    # the Q7 join's shapes: a 2^24-row bucket into its 29,982,720-row output
    "gather_windowed_q7": (
        lambda s, i: gather_windowed_pallas(s, i, interpret=False),
        [((1 << 24,), I32), ((29_982_720,), I32)]),
    "lower_bound_windowed": (
        lambda b, p, w: lower_bound_windowed_pallas(
            b, p, w, window_rows=TILE, tile=TILE, interpret=False),
        [((ROWS,), I32), ((ROWS,), I32), ((ROWS // TILE,), I32)]),
    "hash_probe": (
        lambda bk, off, pk, part: hash_probe_pallas(bk, off, pk, part,
                                                    interpret=False),
        [((PARTS, CAP), I32), ((PARTS,), I32),
         ((N_PROBE_BLOCKS, CAP), I32), ((N_PROBE_BLOCKS,), I32)]),
    "probe_agg": (
        lambda bk, bv, pk, gk, pv, part: probe_agg_pallas(
            bk, bv, pk, gk, pv, part,
            col_sides=(("build", 0), ("probe", 0)), interpret=False),
        [((PARTS, CAP), I32), ((PARTS, 1, CAP), F32),
         ((N_PROBE_BLOCKS, CAP), I32), ((N_PROBE_BLOCKS, CAP), I32),
         ((N_PROBE_BLOCKS, 1, CAP), F32), ((N_PROBE_BLOCKS,), I32)]),
    "segsum_partials": (
        lambda k, v: segsum_partials_pallas(k, v, interpret=False),
        [((ROWS,), I32), ((ROWS,), F32)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = KERNELS[name]
    _compile(fn, one_chip, *shapes)


# each kernel's `pallas_call(name=...)` -> an entry of KERNELS that runs it
NAMED = {
    "block_histograms": "block_histograms",
    "partition_ranks": "partition_ranks",
    "histogram": "histogram",
    "clustered_gather": "gather_windowed_i32",
    "merge_lower_bound": "lower_bound_windowed",
    "hash_probe": "hash_probe",
    "probe_agg": "probe_agg",
    "segsum_partials": "segsum_partials",
}


@pytest.mark.parametrize("kernel", sorted(NAMED))
def test_compiled_kernel_carries_its_name(one_chip, kernel):
    fn, shapes = KERNELS[NAMED[kernel]]
    text = _compile(fn, one_chip, *shapes)
    assert re.search(rf'%{kernel}(\.\d+)? = .*custom_call_target="tpu_custom_call"',
                     text), f"no tpu_custom_call named {kernel}"
