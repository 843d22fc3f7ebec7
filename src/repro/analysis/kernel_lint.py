"""Pallas contract checker: every kernel's compiled shape is statically
auditable from its `pallas_call` eqn, before anything runs on a device.

Three checks per kernel (DESIGN.md §11):

  * VMEM fit — Σ block_shape × itemsize over every block mapping, times a
    double-buffering factor, must fit the per-backend VMEM budget. A block
    spec that exceeds it compiles fine in interpret mode and then OOMs the
    first time it meets real silicon.
  * Grid-output aliasing — two grid steps whose output index_map lands on
    the same block. On GPU-style parallel grids this is the CUDA-atomics
    race the paper works around; on TPU the grid is sequential so a kernel
    may *deliberately* revisit a block to accumulate (histogram does), but
    it must declare that (`allow_output_revisit`) so the hazard is a
    stated contract instead of an accident. Output index_maps that depend
    on scalar-prefetch data are flagged too: their injectivity cannot be
    proven statically.
  * Scatter discipline — kernel bodies must not contain float scatter-add
    primitives (non-deterministic accumulation order on parallel
    backends); the repo's kernels accumulate via one-hot matmuls and
    sorted segmented sums instead (DESIGN.md §2).
"""
from __future__ import annotations

import dataclasses
import itertools
from collections.abc import Collection

import jax
from jax.extend import core as jcore
import numpy as np

from .contracts import FloatScatterViolation, GridAliasViolation, VmemBudgetViolation
from .jaxpr_audit import SCATTER_COMBINE_PRIMS, _as_jaxpr, _is_float, walk_eqns

# Per-backend VMEM budgets (bytes). v5e cores carry ~16 MiB of VMEM
# (pallas guide); leave headroom for the compiler's own scratch.
VMEM_BUDGETS = {"tpu_v5e": 16 * 2**20, "tpu_v4": 16 * 2**20}
DEFAULT_BACKEND = "tpu_v5e"
DOUBLE_BUFFER = 2  # pipelined grids keep two copies of each block in flight
MAX_GRID_POINTS = 1 << 14  # cap on exhaustive index_map enumeration


@dataclasses.dataclass
class KernelLintReport:
    """One pallas_call, statically judged."""
    name: str
    grid: tuple
    vmem_bytes: int
    vmem_budget: int
    aliased_output_blocks: int
    data_dependent_output_map: bool
    kernel_scatter_adds: int
    violations: list

    def as_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["violations"] = [f"{type(v).__name__}: {v}" for v in self.violations]
        d["grid"] = list(self.grid)
        return d


def _block_bytes(bm) -> int:
    aval = bm.block_aval  # the kernel's view of one block (squeezed dims dropped)
    return int(np.prod(aval.shape, dtype=np.int64)) * np.dtype(aval.dtype).itemsize


def _is_output(bm, index: int, num_inputs: int) -> bool:
    origin = str(getattr(bm, "origin", ""))
    if "output" in origin:
        return True
    if "input" in origin or "arg" in origin:
        return False
    return index >= num_inputs


def _depends_on(jaxpr, tainted_vars) -> bool:
    """True if any jaxpr output is data-dependent on `tainted_vars`."""
    tainted = set(map(id, tainted_vars))
    for eqn in jaxpr.eqns:
        if any(not isinstance(v, jcore.Literal) and id(v) in tainted
               for v in eqn.invars):
            tainted.update(id(v) for v in eqn.outvars)
    return any(not isinstance(v, jcore.Literal) and id(v) in tainted
               for v in jaxpr.outvars)


def _eval_index_map(closed, grid_point, extra_avals):
    dummies = [np.zeros(a.shape, a.dtype) for a in extra_avals]
    outs = jcore.jaxpr_as_fun(closed)(*map(np.int32, grid_point), *dummies)
    return tuple(int(np.asarray(o)) for o in outs)


def lint_pallas_eqn(eqn, *, name: str, backend: str = DEFAULT_BACKEND,
                    allow_output_revisit: bool = False) -> KernelLintReport:
    gm = eqn.params["grid_mapping"]
    grid = tuple(int(g) for g in gm.grid)
    budget = VMEM_BUDGETS.get(backend, VMEM_BUDGETS[DEFAULT_BACKEND])
    violations: list = []

    vmem = DOUBLE_BUFFER * sum(_block_bytes(bm) for bm in gm.block_mappings)
    if vmem > budget:
        violations.append(VmemBudgetViolation(
            f"{name}: blocks need {vmem} bytes of VMEM "
            f"(x{DOUBLE_BUFFER} double-buffered) vs {budget} on {backend}"))

    # output index_map injectivity over the full grid
    num_inputs = int(getattr(gm, "num_inputs", len(gm.block_mappings)))
    aliased = 0
    data_dependent = False
    n_points = 1
    for g in grid:
        n_points *= max(g, 1)
    for i, bm in enumerate(gm.block_mappings):
        if not _is_output(bm, i, num_inputs):
            continue
        closed = bm.index_map_jaxpr
        invars = closed.jaxpr.invars
        extra = invars[len(grid):]  # scalar-prefetch operands
        if extra and _depends_on(closed.jaxpr, extra):
            data_dependent = True
            if not allow_output_revisit:
                violations.append(GridAliasViolation(
                    f"{name}: output block map depends on runtime data — "
                    f"grid-step injectivity is unprovable statically"))
            continue
        if n_points > MAX_GRID_POINTS:
            continue  # enumeration capped; report stays informational
        seen: dict = {}
        for point in itertools.product(*(range(g) for g in grid)):
            block = _eval_index_map(closed, point,
                                    [v.aval for v in extra])
            if block in seen:
                aliased += 1
                if not allow_output_revisit:
                    violations.append(GridAliasViolation(
                        f"{name}: grid steps {seen[block]} and {point} both "
                        f"write output block {block} — accumulation must be "
                        f"declared (allow_output_revisit) or the map made "
                        f"injective"))
                break
            seen[block] = point

    # scatter discipline inside the kernel body
    body = _as_jaxpr(eqn.params["jaxpr"])
    scatter_adds = 0
    for sub in walk_eqns(body):
        if sub.primitive.name in SCATTER_COMBINE_PRIMS:
            scatter_adds += 1
            if any(_is_float(v.aval) for v in sub.outvars):
                violations.append(FloatScatterViolation(
                    f"{name}: float scatter-add inside the kernel body — "
                    f"accumulate via one-hot matmul or sorted segmented sum "
                    f"(DESIGN.md §2)"))
    return KernelLintReport(
        name=name, grid=grid, vmem_bytes=vmem, vmem_budget=budget,
        aliased_output_blocks=aliased,
        data_dependent_output_map=data_dependent,
        kernel_scatter_adds=scatter_adds, violations=violations)


def _kernel_name(eqn) -> str:
    """The kernel's name: its `pallas_call(name=...)` (`histogram`), else
    its body's function name, or ''."""
    info = getattr(eqn.params["jaxpr"], "debug_info", None)
    return str(getattr(info, "func_src_info", "") or "").split(" ")[0]


def lint_fn(fn, *args, name: str | None = None,
            backend: str = DEFAULT_BACKEND,
            allow_output_revisit: bool | Collection[str] = False,
            **kwargs) -> list[KernelLintReport]:
    """Trace `fn(*args, **kwargs)` and lint every pallas_call inside.
    `allow_output_revisit` is True for every kernel, or the names of the
    kernels (`_kernel_name`) that revisit output blocks by design; the
    others stay checked."""
    # close over the args: static ints (num_bins, tile sizes) must reach
    # the kernel wrapper as Python values, not tracers
    closed = jax.make_jaxpr(lambda: fn(*args, **kwargs))()
    label = name or getattr(fn, "__name__", "pallas_fn")
    reports = []
    for i, eqn in enumerate(walk_eqns(closed.jaxpr)):
        if eqn.primitive.name != "pallas_call":
            continue
        kname = _kernel_name(eqn)
        allow = (allow_output_revisit if isinstance(allow_output_revisit, bool)
                 else kname in allow_output_revisit)
        reports.append(lint_pallas_eqn(
            eqn, name=f"{label}/{kname or i}", backend=backend,
            allow_output_revisit=allow))
    return reports


def enforce(reports: list[KernelLintReport]) -> None:
    for rep in reports:
        if rep.violations:
            raise rep.violations[0]


# ---------------------------------------------------------------------------
# production registry: every kernel in src/repro/kernels, representative
# shapes, with intentional hazards declared
# ---------------------------------------------------------------------------
def production_kernel_specs():
    """(name, thunk, revisiting kernels) for every production kernel.
    Thunks build (fn, args, kwargs) at call time so jax only initializes
    when the sweep runs. The histogram kernels declare output revisiting:
    `histogram` accumulates its single output block across the
    (sequential) TPU grid, and `block_histograms` (also run by
    partition_ranks) shares one (bins, 128) output block between 128
    consecutive grid steps, each writing its own lane column. Every other
    kernel, partition_ranks' own `partition_ranks` kernel among them, is
    checked."""
    import jax.numpy as jnp

    from repro.kernels.gather import gather_windowed_pallas
    from repro.kernels.hash_probe import hash_probe_pallas, probe_agg_pallas
    from repro.kernels.histogram import histogram_pallas
    from repro.kernels.merge_join import lower_bound_windowed_pallas
    from repro.kernels.radix_partition import (block_histograms_pallas,
                                               partition_ranks_pallas)
    from repro.kernels.segsum import segsum_partials_pallas

    def i32(x):
        return jnp.asarray(x, jnp.int32)

    def digits():
        # 4 blocks of 64x128 digits -> a 4-step grid, so the intentional
        # output revisiting (sequential accumulation) is actually exercised
        return i32(np.arange(4 * 64 * 128) % 16)

    def probe_layout():
        bkeys = i32(np.arange(4 * 128).reshape(4, 128))
        off_r = i32([0, 128, 256, 384])
        pk = i32(np.arange(6 * 128).reshape(6, 128) % 512)
        part = i32([0, 0, 1, 2, 3, 3])
        return bkeys, off_r, pk, part

    def probe_agg_args():
        bkeys, off_r, pk, part = probe_layout()
        bvals = jnp.ones((4, 1, 128), jnp.float32)
        gkb = pk % 64
        pvb = jnp.ones((6, 1, 128), jnp.float32)
        return (bkeys, bvals, pk, gkb, pvb, part)

    return [
        ("histogram", lambda: (histogram_pallas, (digits(), 16), {}),
         ("histogram",)),
        ("block_histograms",
         lambda: (block_histograms_pallas, (digits(), 16), {}),
         ("block_histograms",)),
        ("partition_ranks",
         lambda: (partition_ranks_pallas, (digits(), 16), {}),
         ("block_histograms",)),
        ("segsum_partials",
         lambda: (segsum_partials_pallas,
                  (i32(np.sort(np.arange(1024) % 64)),
                   jnp.ones((1024,), jnp.float32)), {}), ()),
        ("gather_windowed",
         lambda: (gather_windowed_pallas,
                  (jnp.ones((4096,), jnp.float32), i32(np.arange(2048))),
                  {}), ()),
        ("lower_bound_windowed",
         lambda: (lower_bound_windowed_pallas,
                  (i32(np.arange(2048)), i32(np.arange(2048)),
                   i32([0, 1])), {}), ()),
        ("hash_probe",
         lambda: (hash_probe_pallas, probe_layout(), {}), ()),
        ("probe_agg",
         lambda: (probe_agg_pallas, probe_agg_args(),
                  {"col_sides": (("build", 0), ("probe", 0))}), ()),
    ]


def lint_production_kernels(backend: str = DEFAULT_BACKEND):
    """Lint every registered production kernel; returns all reports."""
    reports = []
    for kname, thunk, allow in production_kernel_specs():
        fn, args, kwargs = thunk()
        reports.extend(lint_fn(fn, *args, name=kname, backend=backend,
                               allow_output_revisit=allow, **kwargs))
    return reports
