"""The reference against the served path, the comparison, and the least
bytes, at tiny sizes on the CPU (Pallas kernels in interpret mode)."""
import json

import numpy as np
import pytest

import datagen
import plans
import reference
import run
from conftest import tiny

TRAFFIC = ("join-groupby", "join", "groupby")
CONFIGS = ("tpch-q18-sf10", "tpch-q7-sf10")


def cell_for(config: str, traffic: str, divisor: int = 10_000) -> dict:
    """A cell of any configuration and traffic, cut to a tiny size."""
    bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    name = f"{config}.{traffic}"
    bench["workloads"] = [{"name": name, "config": config, "traffic": traffic,
                           "chips": 1, "why": "test"}]
    cell = run.load_cell(name, bench)
    for table in cell["config"]["tables"].values():
        table["rows"] = max(table["rows"] // divisor, 64)
    return cell


@pytest.mark.parametrize("config", CONFIGS)
@pytest.mark.parametrize("traffic", TRAFFIC)
def test_reference_agrees_with_the_served_answers(cpu_run, config, traffic):
    out = cpu_run(cell_for(config, traffic))
    assert out["correct"], out
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert out["check"] == {"rows_off": {"value": 0, "limit": 0},
                            "count_gap": {"value": 0, "limit": 0}}
    assert set(out["metrics"]) == {"query_s", "peak_hbm_gb", "setup_s"}
    assert list(out)[-1] == "check"


@pytest.mark.parametrize("key_seed", [1, 2**31 + 9])
@pytest.mark.parametrize("config", CONFIGS)
def test_reference_agrees_on_other_key_multisets(cpu_run, monkeypatch, config,
                                                 key_seed):
    """Runs keep one key multiset; the served path has to agree with the
    reference on others too (other group sizes and partition loads)."""
    monkeypatch.setattr(datagen, "KEY_SEED", key_seed)
    out = cpu_run(cell_for(config, "join-groupby"))
    assert out["correct"], out


def test_the_window_starts_with_no_answer_held(cpu_run, monkeypatch):
    """Set-up's answer is released before the window, so the device's peak
    counts the window's own answers only."""
    measure, held = run.measure, []

    def spy(server, *args):
        held.append([r.result for r in server.completed])
        return measure(server, *args)

    monkeypatch.setattr(run, "measure", spy)
    out = cpu_run(cell_for("tpch-q7-sf10", "join"))
    assert out["correct"] and held == [[None]]


def test_comparison_counts_wrong_missing_and_extra_rows():
    rows = {"k": np.array([3, 1, 2, 2], np.int32),
            "v": np.array([7, 8, 9, 9], np.int32)}
    exp = reference.Expected(rows)
    perm = {c: v[[2, 0, 3, 1]] for c, v in rows.items()}
    assert exp.compare(perm, 4) == {"rows_off": 0, "count_gap": 0}
    wrong = dict(perm, v=perm["v"] + np.array([0, 1, 0, 0], np.int32))
    assert exp.compare(wrong, 4) == {"rows_off": 1, "count_gap": 0}
    assert exp.compare(perm, 3)["rows_off"] == 1
    assert exp.compare(perm, 3)["count_gap"] == 1
    extra = {c: np.r_[v, v[:1]] for c, v in perm.items()}
    assert exp.compare(extra, 5) == {"rows_off": 1, "count_gap": 1}
    assert exp.compare({"k": perm["k"]}, 4)["rows_off"] == 4


def test_reference_join_and_group_by_by_hand():
    tables = {"R": {"k": np.array([2, 0, 1], np.int32),
                    "r1": np.array([20, 0, 10], np.int32)},
              "S": {"k": np.array([1, 1, 2, 5], np.int32),
                    "s1": np.array([3, 4, 5, 6], np.int32)}}
    steps = [["scan", "S"], ["join", {"table": "R", "key": "k"}]]
    got = reference.evaluate(steps, tables)
    assert sorted(zip(got["k"], got["s1"], got["r1"])) == [
        (1, 3, 10), (1, 4, 10), (2, 5, 20)]
    steps.append(["group_by", {"key": "k", "aggs": {"s1": "sum", "r1": "sum"}}])
    got = reference.evaluate(steps, tables)
    assert got["k"].tolist() == [1, 2]
    assert got["s1_sum"].tolist() == [7, 5] and got["r1_sum"].tolist() == [20, 20]
    big = {"k": np.zeros(3, np.int32), "v": np.full(3, 2**30, np.int32)}
    wrapped = reference.evaluate([["scan", "T"], ["group_by", {
        "key": "k", "aggs": {"v": "sum"}}]], {"T": big})
    assert wrapped["v_sum"].tolist() == [np.int32(-(2**30))]  # 3 * 2^30 wraps


def test_bfloat16_rounding():
    v = np.array([0, 1, 255, 256, 257, 258, 5000, 5100, -300, 2**24 + 1], np.int32)
    want = [0, 1, 255, 256, 256, 258, 4992, 5088, -300, 2**24]
    assert reference.to_bfloat16(v).tolist() == want


def test_least_bytes_by_hand():
    cell = run.load_cell("tpch-q18-sf10.join-groupby")
    steps = cell["traffic"]["plan"]
    schemas = {"R": ["k", "r1", "r2_hi", "r2_lo", "r3_hi", "r3_lo"],
               "S": ["k", "s1_hi", "s1_lo"]}
    rows = {"R": 15_000_000, "S": 60_000_000}
    # lineitem's k and both s1 words, orders' k; groups x (k, two sums)
    want = 4 * (60_000_000 * 3 + 15_000_000 * 1 + 14_726_000 * 3)
    assert plans.least_bytes(steps, rows, schemas, 14_726_000) == want
    join = [["scan", "S"], ["join", {"table": "R", "key": "k"}]]
    # every column of both tables in, 3 + 5 columns out per lineitem row
    want = 4 * (60_000_000 * 3 + 15_000_000 * 6 + 60_000_000 * 8)
    assert plans.least_bytes(join, rows, schemas, 60_000_000) == want
