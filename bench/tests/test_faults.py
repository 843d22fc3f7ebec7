"""A run with the timed path broken underneath has to come out not
correct: the harness's chip check is skipped, everything else runs, and
the fault is planted where the server produces its answer."""
import jax.numpy as jnp
import pytest

from conftest import CELLS, tiny
from repro.core.table import Table
from repro.serve.query import QueryServer


def altered(out):
    """One value of the first row's first non-key column changed."""
    table, count = out
    col = sorted(c for c in table.column_names if c != "k")[0]
    return Table(dict(table.columns, **{col: table[col].at[0].add(1)})), count


def half_left_out(out):
    """Half of the rows dropped from the answer."""
    table, count = out
    return table, count // 2


def input_unchanged(tables):
    """The probe table handed back as if it were the answer."""
    probe = tables["S"]
    return probe, jnp.int32(probe.num_rows)


FAULTS = {"altered": altered, "half_left_out": half_left_out}


@pytest.mark.parametrize("fault", [*FAULTS, "input_unchanged"])
@pytest.mark.parametrize("name", CELLS)
def test_broken_answers_are_not_correct(cpu_run, monkeypatch, name, fault):
    fast = QueryServer._run_fast

    def broken(self, entry, req):
        if fault == "input_unchanged":
            return input_unchanged(req.tables)
        return FAULTS[fault](fast(self, entry, req))

    cell = tiny(name)
    monkeypatch.setattr(QueryServer, "_run_fast", broken)
    out = cpu_run(cell)
    assert out["correct"] is False, out
    assert out["check"]["rows_off"]["value"] > 0


@pytest.mark.parametrize("name", CELLS)
def test_a_failed_query_is_not_correct(cpu_run, monkeypatch, name):
    fast = QueryServer._run_fast
    calls = []

    def fails_after_warm_up(self, entry, req):
        calls.append(req.qid)
        if len(calls) > 1:
            raise RuntimeError("planted kernel failure")
        return fast(self, entry, req)

    monkeypatch.setattr(QueryServer, "_run_fast", fails_after_warm_up)
    out = cpu_run(tiny(name))
    assert out["correct"] is False and out["failed"] == out["attempted"] > 0
