"""chip_smoke.py's serving loop and NumPy references, exercised on the CPU
at a tiny scale so the smoke's path and its reference stay correct on every
change without a chip."""
from __future__ import annotations

import importlib.util
import pathlib

import jax
import numpy as np
import pytest

from repro import compile_cache
from repro.serve.query import QueryServer

_PATH = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_requests_match_numpy_reference(smoke):
    server = QueryServer()
    records = smoke.serve_and_check(server, seed=7, requests=2, scale=1e-4,
                                    log=lambda *_: None)
    assert [r["query"] for r in records] == ["join", "groupby"] * 2
    for r in records:
        assert r["path"] == "fast" and not r["error"], r
        assert not r["mismatch"], r
    # one signature per query: the second dataset reused its executable
    assert len(server.cache) == 2
    assert all(e.hits == 1 for e in server.cache.values())


def test_smoke_references_catch_wrong_answers(smoke):
    rng = np.random.default_rng(0)
    R = {"k": rng.permutation(50).astype(np.int32),
         "r1": rng.integers(0, 9, 50).astype(np.int32)}
    S = {"k": rng.integers(0, 60, 200).astype(np.int32),
         "s1": rng.integers(0, 1 << 30, 200).astype(np.int32)}
    ref = smoke.reference_join(R, S)
    m = S["k"] < 50
    pos = {int(k): i for i, k in enumerate(R["k"])}
    src = np.array([pos[int(k)] for k in S["k"][m]])
    got = {"k": S["k"][m], "s1": S["s1"][m], "r1": R["r1"][src]}
    perm = rng.permutation(len(src))  # order-insensitive
    got = {c: v[perm] for c, v in got.items()}
    assert smoke.check_join(got, len(src), ref) == ""
    bad = dict(got, r1=got["r1"].copy())
    bad["r1"][0] += 1
    assert "r1" in smoke.check_join(bad, len(src), ref)
    assert "rows" in smoke.check_join(got, len(src) - 1, ref)

    present, sums = smoke.reference_groupby(R, S)
    keys = np.flatnonzero(present).astype(np.int32)
    got = {"k": keys[::-1], "s1_sum": sums[keys][::-1]}
    assert smoke.check_groupby(got, len(keys), (present, sums)) == ""
    wrong = dict(got, s1_sum=got["s1_sum"] + 1)
    assert "s1_sum" in smoke.check_groupby(wrong, len(keys), (present, sums))


def test_smoke_refuses_a_host_without_tpu(smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) != 0
    assert capsys.readouterr().out == ""


def test_compile_cache_placement(monkeypatch):
    keys = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    try:
        monkeypatch.setenv(compile_cache.ENV_VAR, "/elsewhere/cache")
        jax.config.update("jax_compilation_cache_dir", None)
        # set after jax's import, the variable must still place the cache
        assert compile_cache.enable() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == "/elsewhere/cache"
        assert jax.config.jax_enable_compilation_cache
        monkeypatch.delenv(compile_cache.ENV_VAR)
        path = compile_cache.enable()
        assert path == str(_PATH.parent / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
