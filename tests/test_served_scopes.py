"""The served path measures itself: host spans at the work (`metrics.span`)
and node/phase scopes on the compiled program (`executor.scope_map`), on
the ahead-of-time executable the server runs."""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Table
from repro.engine import Catalog, executor, optimize, scan
from repro.engine import physical as P
from repro.obs import metrics
from repro.serve import query as Q

SPANS = ("qserve.signature", "qserve.pad", "qserve.dispatch",
         "qserve.count_sync", "plan.stats", "plan.audit", "exec.compile",
         "exec.run")


def tables(n_r, n_s, groups=50, seed=0):
    """PK-FK relations: R's keys 0..n_r-1 shuffled, S's keys uniform."""
    rng = np.random.default_rng(seed)
    col = lambda a: jnp.asarray(a.astype(np.int32))
    R = Table({"k": col(rng.permutation(n_r)), "rv": col(rng.integers(0, 100, n_r))})
    S = Table({"k": col(rng.integers(0, n_r, n_s)), "g": col(rng.integers(0, groups, n_s)),
               "sv": col(rng.integers(0, 100, n_s))})
    return {"R": R, "S": S}


def canon(table, count):
    n = int(count)
    cols = sorted(table.column_names)
    return tuple(cols), sorted(zip(*[np.asarray(table[c])[:n].tolist() for c in cols]))


def served(plan, tbs, **root_changes):
    """(plan, padded inputs, counts) as the server runs them; `root_changes`
    replace fields of the optimizer's root node (a forced strategy)."""
    _, buckets = Q.plan_signature(plan, tbs)
    padded = {n: Q.pad_table(t, buckets[n]) for n, t in tbs.items()}
    phys = optimize(plan, Catalog(padded), measure_profile=False)
    if root_changes:
        phys = P.PhysicalPlan(root=dataclasses.replace(phys.root, **root_changes),
                              catalog=phys.catalog, total_cost=phys.total_cost)
    return phys, padded, {n: t.num_rows for n, t in tbs.items()}


def test_a_served_query_records_every_span():
    before = {name: metrics.histogram(name).count for name in SPANS}
    server = Q.QueryServer()
    plan = scan("S").join(scan("R"), key="k").group_by("k", sv="sum")
    req = Q.QueryRequest(qid=0, plan=plan, tables=tables(300, 1000))
    server.submit(req)
    server.run()
    assert req.done and req.path == "fast", (req.error, req.detail)
    for name in SPANS:
        h = metrics.histogram(name)
        assert h.count > before[name] and h.total > 0, name


JOIN = scan("S").join(scan("R"), key="k")
GROUP = scan("S").group_by("k", sv="sum")
GROUPJOIN = scan("S").join(scan("R"), key="k").group_by("g", rv="sum")
# node scope -> (query, root changes, phases its program shows). A fusion
# carries its root's scope: the sort group-by's payload gather fuses into
# its segmented sum on the CPU, so only partition and aggregate show there,
# and the group-join's build-value gather likewise.
CASES = {
    "join.phj": (JOIN, {}, ("partition", "probe", "materialize")),
    "groupby.sort": (GROUP, {"strategy": "sort", "agg_kw": ()},
                     ("partition", "aggregate")),
    "groupby.partition": (GROUP, {"strategy": "partition", "agg_kw": ()},
                          ("partition", "materialize", "aggregate")),
    "groupjoin.phj": (GROUPJOIN, {}, ("partition", "probe", "aggregate")),
}


@pytest.mark.parametrize("node", sorted(CASES))
def test_served_executable_carries_node_and_phase_scopes(node):
    query, changes, phases = CASES[node]
    # power-of-two sizes: no padding rows, so the match ratio stays 1 and
    # the optimizer fuses the group-join
    phys, padded, counts = served(query, tables(2048, 16384), **changes)
    assert phys.root.describe().lower().startswith(node.split(".")[0]), phys.explain()
    phys.run(padded, counts=counts)
    (prog,) = phys.compiled_bucketed.values()
    paths = set(executor.scope_map(prog.compiled.as_text()).values())
    assert {f"{node}/{ph}" for ph in phases} <= paths, sorted(paths)
    # the trace metadata carries the same map, in its reserved-free format
    assert all(c not in prog.scopes for c in ",=#")
    decoded = {name: grp.split(" ")[0] for grp in prog.scopes.split("|")
               for name in grp.split(" ")[1:]}
    assert decoded == executor.scope_map(prog.compiled.as_text())
    assert prog.module == "jit_served_plan"


def test_served_program_compiles_once_per_input_shape():
    """The ahead-of-time executable answers as the one-shot path does, is
    compiled once per bucketed input shape and reused after that."""
    plan = scan("S").join(scan("R"), key="k").group_by("k", sv="sum")
    small, large = tables(300, 1000, seed=1), tables(600, 3000, seed=3)
    phys, _, _ = served(plan, large)  # its capacities hold the small inputs
    compiles = metrics.histogram("exec.compile").count
    for tbs in (small, tables(310, 900, seed=2), large, small):
        _, buckets = Q.plan_signature(plan, tbs)
        padded = {n: Q.pad_table(t, buckets[n]) for n, t in tbs.items()}
        got = phys.run(padded, counts={n: t.num_rows for n, t in tbs.items()})
        want = optimize(plan, Catalog(tbs), measure_profile=False).run()
        assert canon(*got) == canon(*want)
    # 300x1000 and 310x900 share buckets; 600x3000 is a second shape
    assert len(phys.compiled_bucketed) == 2
    assert metrics.histogram("exec.compile").count == compiles + 2
