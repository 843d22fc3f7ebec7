"""The on-device generator: deterministic per seed, and the keys and
values each configuration asks for."""
import hashlib

import numpy as np
import pytest

import datagen
from conftest import CELLS, tiny


def host(tables):
    return {n: {c: np.asarray(v) for c, v in t.items()} for n, t in tables.items()}


@pytest.mark.parametrize("name", CELLS)
def test_same_seed_same_tables_other_seed_other_tables(name):
    config = tiny(name)["config"]
    a = host(datagen.generate(config, 2**31 + 7))
    b = host(datagen.generate(config, 2**31 + 7))
    c = host(datagen.generate(config, 2**31 + 8))
    for t in a:
        assert list(a[t]) == datagen.column_names(config["tables"][t])
        for col in a[t]:
            assert a[t][col].dtype == np.int32
            np.testing.assert_array_equal(a[t][col], b[t][col])
        assert any(not np.array_equal(a[t][col], c[t][col]) for col in a[t])
        # the keys' multiset is the seed's to order, not to draw
        k = config["tables"][t]["key"]["name"]
        assert not np.array_equal(a[t][k], c[t][k])
        np.testing.assert_array_equal(np.sort(a[t][k]), np.sort(c[t][k]))


@pytest.mark.parametrize("name", CELLS)
def test_keys_and_values_follow_the_configuration(name):
    config = tiny(name)["config"]
    tables = host(datagen.generate(config, 3))
    R, S = config["tables"]["R"], config["tables"]["S"]
    np.testing.assert_array_equal(np.sort(tables["R"]["k"]), np.arange(R["rows"]))
    fk = tables["S"]["k"]
    assert len(fk) == S["rows"] and fk.min() >= 0 and fk.max() < R["rows"]
    for name_, spec in (("R", R), ("S", S)):
        for attr in spec["attributes"]:
            m = attr.get("multiplier", 1)
            lo_col = attr["name"] if attr["bytes"] == 4 else attr["name"] + "_lo"
            v = tables[name_][lo_col]
            assert v.min() >= attr["low"] * m and v.max() <= attr["high"] * m
            assert np.all(v % m == 0)
            if attr["bytes"] == 8:
                np.testing.assert_array_equal(tables[name_][attr["name"] + "_hi"],
                                              np.where(v < 0, -1, 0))


@pytest.mark.parametrize("name", CELLS)
def test_key_seed_draws_the_key_multisets(monkeypatch, name):
    config = tiny(name)["config"]
    a = host(datagen.generate(config, 5))
    monkeypatch.setattr(datagen, "KEY_SEED", 2**31 + 1)
    b = host(datagen.generate(config, 5))
    fk_a, fk_b = np.sort(a["S"]["k"]), np.sort(b["S"]["k"])
    assert not np.array_equal(fk_a, fk_b)
    assert fk_b.min() >= 0 and fk_b.max() < config["tables"]["R"]["rows"]
    np.testing.assert_array_equal(np.sort(b["R"]["k"]), np.sort(a["R"]["k"]))


# sha256 over every column (tables and columns in sorted order, each name
# then its bytes) of the cells' configurations cut by `tiny`, seed 2^31 + 7:
# the chip readings that the bounds and limits rest on were taken on these
# tables, so a change to the generator may not alter them
TINY_TABLES_SHA256 = {
    "tpch-q18-sf10.join-groupby":
        "e59e4eed67dd4f42507881f707927e06cfb96174aa494b8e659b87efa03278cf",
    "tpch-q7-sf10.join":
        "7d5d7585feb66515c133dbcbef8bd6785f865ef261da463c0aaded99f760e628",
}


@pytest.mark.parametrize("name", TINY_TABLES_SHA256)
def test_the_configurations_tables_are_unchanged(name):
    tables = host(datagen.generate(tiny(name)["config"], 2**31 + 7))
    h = hashlib.sha256()
    for t in sorted(tables):
        for c in sorted(tables[t]):
            h.update(f"{t}.{c}".encode())
            h.update(tables[t][c].tobytes())
    assert h.hexdigest() == TINY_TABLES_SHA256[name]
