"""The m:n equi-join: the generator's shared key domain, the reference's
m:n join against a brute-force one, and an m:n cell served on the CPU."""
import collections
import copy

import numpy as np
import pytest

import control
import datagen
import reference
import run
from test_datagen import host

JOIN = [["scan", "S"], ["join", {"table": "R", "key": "k"}]]


def brute_force(steps: list, tables: dict) -> list:
    """The join's rows, sorted, each a tuple over its sorted column names:
    every probe row with every build row of the same key, by a dictionary."""
    probe, build = tables[steps[0][1]], tables[steps[1][1]["table"]]
    key = steps[1][1]["key"]
    rows_of = collections.defaultdict(list)
    for j, k in enumerate(build[key]):
        rows_of[int(k)].append(j)
    names = sorted(set(probe) | set(build))
    out = []
    for i, k in enumerate(probe[key]):
        for j in rows_of[int(k)]:
            row = {c: int(v[i]) for c, v in probe.items()}
            row.update({c: int(v[j]) for c, v in build.items() if c not in row})
            out.append(tuple(row[c] for c in names))
    return sorted(out)


def rows(cols: dict) -> list:
    names = sorted(cols)
    return sorted(zip(*(cols[c].tolist() for c in names)))


def keyed(build_keys, probe_keys) -> dict:
    """Tables R (build) and S (probe) with the given keys and a value per
    row that tells the rows apart."""
    r = np.asarray(build_keys, np.int32)
    s = np.asarray(probe_keys, np.int32)
    return {"R": {"k": r, "r1": np.arange(len(r), dtype=np.int32) + 100},
            "S": {"k": s, "s1": np.arange(len(s), dtype=np.int32) + 1000}}


rng = np.random.default_rng(7)
CASES = {
    "duplicates_both_sides": keyed([1, 2, 2, 3, 3, 3, 1], [3, 1, 2, 3, 1, 1, 2]),
    "keys_on_one_side_only": keyed([0, 2, 2, 7, 9, 9], [2, 4, 5, 2, 9, 6, 0]),
    "empty_result": keyed([2, 2, 4], [1, 3, 5, 7]),
    "one_hot_key": keyed([0] * 40 + [1, 2], [0] * 30 + [2, 5] + [0] * 5),
    "random": keyed(rng.integers(0, 50, 400), rng.integers(0, 60, 300)),
}


@pytest.mark.parametrize("block", [reference.PROBE_BLOCK, 3])
@pytest.mark.parametrize("case", CASES)
def test_mn_join_is_the_brute_force_join(monkeypatch, case, block):
    monkeypatch.setattr(reference, "PROBE_BLOCK", block)  # 3: many blocks
    tables = CASES[case]
    got = reference.evaluate(JOIN, tables)
    want = brute_force(JOIN, tables)
    assert set(got) == {"k", "r1", "s1"}
    assert rows(got) == want
    assert (len(want) == 0) == (case == "empty_result")
    # a group-by over the m:n join sums over every pair
    summed = reference.evaluate(JOIN + [["group_by", {
        "key": "k", "aggs": {"r1": "sum"}}]], tables)
    sums = collections.Counter()
    for k, r1, _ in want:
        sums[k] += r1
    assert dict(zip(summed["k"].tolist(), summed["r1_sum"].tolist())) == dict(sums)


def test_unique_build_keys_keep_the_direct_lookup(monkeypatch):
    def no_expand(*args):
        raise AssertionError("a PK-FK join went through the m:n expansion")

    monkeypatch.setattr(reference, "_expand", no_expand)
    tables = keyed([3, 0, 2, 1], [1, 1, 3, 5, 0])
    assert rows(reference.evaluate(JOIN, tables)) == brute_force(JOIN, tables)


def test_sparse_unique_build_keys_take_the_binary_search():
    tables = keyed([7, 10**6, 3 * 10**8], [10**6, 7, 7, 5, 3 * 10**8])
    assert reference._lookup(tables["R"]["k"], tables["S"]["k"]) is None
    assert rows(reference.evaluate(JOIN, tables)) == brute_force(JOIN, tables)


DOMAIN = 1000
MN_CONFIG = {
    "name": "mn-test",
    "tables": {
        "R": {"rows": 4000, "key": {"name": "k", "kind": "foreign", "domain": DOMAIN},
              "attributes": [{"name": "r1", "bytes": 8, "low": 0, "high": 10**7}]},
        "S": {"rows": 4096, "key": {"name": "k", "kind": "foreign", "domain": DOMAIN},
              "attributes": [{"name": "s1", "bytes": 8, "low": 0, "high": 10**7}]},
    },
}


def test_domain_keys_share_one_multiset_per_key_seed(monkeypatch):
    a = host(datagen.generate(MN_CONFIG, 2**31 + 11))
    b = host(datagen.generate(MN_CONFIG, 2**31 + 12))
    monkeypatch.setattr(datagen, "KEY_SEED", 2**31 + 1)
    c = host(datagen.generate(MN_CONFIG, 2**31 + 11))
    for t, spec in MN_CONFIG["tables"].items():
        for tables in (a, b, c):
            k = tables[t]["k"]
            assert len(k) == spec["rows"] and k.min() >= 0 and k.max() < DOMAIN
        assert not np.array_equal(a[t]["k"], b[t]["k"])  # the seed orders
        np.testing.assert_array_equal(np.sort(a[t]["k"]), np.sort(b[t]["k"]))
        assert not np.array_equal(np.sort(a[t]["k"]), np.sort(c[t]["k"]))
    # both sides draw from the one domain, each its own multiset
    assert not np.array_equal(np.sort(a["R"]["k"]), np.sort(a["S"]["k"])[:4000])
    assert len(np.intersect1d(a["R"]["k"], a["S"]["k"])) > DOMAIN * 0.9


def mn_cell() -> dict:
    """An m:n cell: the materialised join's traffic over MN_CONFIG."""
    cell = run.load_cell("tpch-q7-sf10.join")
    assert cell["traffic"]["plan"] == JOIN
    cell["config"] = copy.deepcopy(MN_CONFIG)
    return cell


@pytest.mark.parametrize("key_seed", [datagen.KEY_SEED, 2**31 + 9])
def test_an_mn_cell_is_served_correct(cpu_run, monkeypatch, key_seed):
    monkeypatch.setattr(datagen, "KEY_SEED", key_seed)
    expanded = []
    expand = reference._expand

    def spy(*args):
        expanded.append(expand(*args))
        return expanded[-1]

    monkeypatch.setattr(reference, "_expand", spy)
    out = cpu_run(mn_cell())
    assert out["correct"], out
    assert out["check"] == {"rows_off": {"value": 0, "limit": 0},
                            "count_gap": {"value": 0, "limit": 0}}
    # the reference took the m:n path, and its answer outgrows both sides
    assert len(expanded) == 1 and len(expanded[0]["k"]) > 4096 * 3


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
def test_control_is_refused_on_the_mn_join(seed):
    found = control.readings(mn_cell(), seed)
    assert any(found[k] > run.LIMITS[k] for k in run.LIMITS), found
