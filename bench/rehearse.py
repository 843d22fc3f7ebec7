"""Compile a cell's served program for a described TPU v5e, with no chip.

    JAX_PLATFORMS=cpu python bench/rehearse.py --workload <cell> [--divisor 16]

Plans the cell's query on the CPU over its tables cut to 1/`divisor` of
their rows (the planner reads statistics from the data, and the full size
is not for a CPU), then lowers that plan's bucketed executable with
compiled Pallas kernels for one chip of a described `v5e:2x2` and prints
what the compiler says: the Pallas kernels it holds (`tpu_custom_call`)
and `memory_analysis()`. Nothing runs on a device. The CPU's cost profile
may choose another plan than the chip's planner does.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--divisor", type=int, default=16)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(run.ROOT / "src"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import datagen
    import plans
    from repro.core.table import Table
    from repro.serve.query import QueryRequest, QueryServer

    cell = run.load_cell(args.workload)
    for table in cell["config"]["tables"].values():
        table["rows"] //= args.divisor
    tables = {n: Table(c) for n, c in
              datagen.generate(cell["config"], 0).items()}
    server = QueryServer()
    req = QueryRequest(qid=0, plan=plans.build(cell["traffic"]["plan"]),
                       tables=tables)
    server._ensure_entry(req)  # plans the signature; runs nothing
    entry = server.cache[req.signature]

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    shapes = {n: Table({c: jax.ShapeDtypeStruct((entry.buckets[n],), t[c].dtype,
                                                sharding=chip)
                        for c in t.column_names}) for n, t in tables.items()}
    counts = {n: jax.ShapeDtypeStruct((), "int32", sharding=chip) for n in tables}
    os.environ["REPRO_PALLAS_INTERPRET"] = "0"  # the chip's kernels, compiled
    jax.config.update("jax_enable_compilation_cache", False)
    from repro.engine import executor

    fn = jax.jit(lambda tb, ct: executor.execute(entry.plan.root, tb, ct))
    compiled = fn.lower(shapes, counts).compile()
    mem = compiled.memory_analysis()
    print(entry.plan.explain())
    print(json.dumps({
        "workload": args.workload, "divisor": args.divisor,
        "buckets": entry.buckets, "morsel_factor": entry.morsel_factor,
        "audited_peak_bytes": entry.peak_bytes,
        "tpu_custom_calls": compiled.as_text().count("tpu_custom_call"),
        "memory_analysis": {k: getattr(mem, k) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes")},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
