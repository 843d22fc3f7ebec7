"""Join correctness: every (algorithm x pattern x mode) against a python
oracle, across match ratios, duplicates, skew, sizes, and dtypes.

The oracle is an exact dict-based join; results are compared as sorted
multisets of full rows, so ordering differences between implementations are
irrelevant but any wrong/missing/duplicated row fails."""
from __future__ import annotations

import collections

from hypothesis import given, settings, strategies as st
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import KEY_SENTINEL, Table, join, join_sequence

ALGS_PATTERNS = [
    ("smj", "gfur"), ("smj", "gftr"),
    ("phj", "gfur"), ("phj", "gftr"),
    ("nphj", "gftr"),
]


def oracle_join(rkeys, rpays, skeys, spays):
    """Exact inner PK-FK/m:n join -> sorted list of row tuples."""
    rmap = collections.defaultdict(list)
    for i, k in enumerate(rkeys):
        rmap[int(k)].append(i)
    rows = []
    for j, k in enumerate(skeys):
        for i in rmap.get(int(k), ()):
            rows.append((int(k),) + tuple(int(p[i]) for p in rpays)
                        + tuple(int(p[j]) for p in spays))
    return sorted(rows)


def result_rows(T, count, r_cols, s_cols):
    c = int(count)
    cols = [np.asarray(T["k"][:c])] + [np.asarray(T[n][:c]) for n in r_cols + s_cols]
    return sorted(zip(*[c_.tolist() for c_ in cols]))


def make_tables(rng, n_r, n_s, r_pay, s_pay, match_ratio=1.0, dup_build=False,
                dtype=np.int32):
    rkeys = rng.permutation(n_r).astype(dtype)
    if dup_build:
        rkeys = rng.integers(0, max(n_r // 4, 1), n_r).astype(dtype)
    if match_ratio < 1.0:
        drop = rng.random(n_r) < (1 - match_ratio)
        rkeys = np.where(drop, (np.arange(n_r) + 10 * n_r + 7).astype(dtype), rkeys)
    skeys = rng.integers(0, n_r, n_s).astype(dtype)
    R = {"k": jnp.asarray(rkeys)}
    rp = []
    for i in range(r_pay):
        R[f"r{i}"] = jnp.asarray(rng.integers(0, 1 << 20, n_r).astype(dtype))
        rp.append(np.asarray(R[f"r{i}"]))
    S = {"k": jnp.asarray(skeys)}
    sp = []
    for i in range(s_pay):
        S[f"s{i}"] = jnp.asarray(rng.integers(0, 1 << 20, n_s).astype(dtype))
        sp.append(np.asarray(S[f"s{i}"]))
    return Table(R), Table(S), rkeys, rp, skeys, sp


@pytest.mark.parametrize("alg,pattern", ALGS_PATTERNS)
@pytest.mark.parametrize("match_ratio", [1.0, 0.5, 0.0])
def test_pk_fk_join(alg, pattern, match_ratio, rng):
    R, S, rk, rp, sk, sp = make_tables(rng, 700, 1900, 2, 1, match_ratio)
    expected = oracle_join(rk, rp, sk, sp)
    T, count = join(R, S, algorithm=alg, pattern=pattern, out_size=1900)
    got = result_rows(T, count, ["r0", "r1"], ["s0"])
    assert int(count) == len(expected)
    assert got == expected
    # padding rows carry the sentinel
    assert bool((np.asarray(T["k"][int(count):]) == KEY_SENTINEL).all())


@pytest.mark.parametrize("alg", ["smj", "phj"])
@pytest.mark.parametrize("pattern", ["gfur", "gftr"])
def test_mn_join_with_duplicates(alg, pattern, rng):
    R, S, rk, rp, sk, sp = make_tables(rng, 400, 600, 1, 1, dup_build=True)
    expected = oracle_join(rk, rp, sk, sp)
    T, count = join(R, S, algorithm=alg, pattern=pattern, mode="mn",
                    out_size=len(expected) + 64)
    got = result_rows(T, count, ["r0"], ["s0"])
    assert int(count) == len(expected)
    assert got == expected


@pytest.mark.parametrize("alg,pattern", ALGS_PATTERNS)
def test_skewed_foreign_keys(alg, pattern, rng):
    n_r, n_s = 500, 3000
    rkeys = rng.permutation(n_r).astype(np.int32)
    ranks = rng.zipf(1.5, n_s).astype(np.int64)
    skeys = ((ranks - 1) % n_r).astype(np.int32)
    R = Table({"k": jnp.asarray(rkeys), "r0": jnp.asarray(rkeys * 3)})
    S = Table({"k": jnp.asarray(skeys), "s0": jnp.asarray(skeys * 7)})
    expected = oracle_join(rkeys, [np.asarray(R["r0"])], skeys, [np.asarray(S["s0"])])
    T, count = join(R, S, algorithm=alg, pattern=pattern, out_size=n_s)
    assert result_rows(T, count, ["r0"], ["s0"]) == expected


def test_out_size_truncation(rng):
    R, S, rk, rp, sk, sp = make_tables(rng, 100, 500, 1, 1)
    T, count = join(R, S, algorithm="phj", pattern="gftr", out_size=64)
    assert int(count) == 64  # clamped to capacity
    assert T["k"].shape[0] == 64


def test_empty_payloads_narrow_join(rng):
    """Narrow join (keys only on one side)."""
    R, S, rk, rp, sk, sp = make_tables(rng, 300, 800, 0, 1)
    expected = oracle_join(rk, [], sk, sp)
    T, count = join(R, S, algorithm="smj", pattern="gftr")
    assert result_rows(T, count, [], ["s0"]) == expected


def test_kernel_backed_paths_match_xla(rng):
    R, S, rk, rp, sk, sp = make_tables(rng, 800, 2200, 2, 1)
    expected = oracle_join(rk, rp, sk, sp)
    T1, c1 = join(R, S, algorithm="smj", pattern="gftr", find_impl="pallas")
    T2, c2 = join(R, S, algorithm="phj", pattern="gftr",
                  probe_impl="pallas")
    assert result_rows(T1, c1, ["r0", "r1"], ["s0"]) == expected
    assert result_rows(T2, c2, ["r0", "r1"], ["s0"]) == expected


@pytest.mark.parametrize("alg", ["phj", "smj"])
def test_gftr_clustered_gather_kernel_matches_the_xla_arm(alg, rng, monkeypatch):
    """GFTR's clustered output gathers through the windowed kernel give the
    XLA arm's output row for row, the fill of the rows past the count too."""
    import jax

    from repro.kernels import ops

    R, S, *_ = make_tables(rng, 3000, 5000, 3, 2, match_ratio=0.7)
    run = lambda: jax.jit(lambda R, S: join(R, S, algorithm=alg,
                                            pattern="gftr"))(R, S)
    T1, c1 = run()
    calls = []
    real = ops.gather_windowed_pallas
    monkeypatch.setattr(ops, "gather_windowed_pallas",
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    # as on a backend that compiles Pallas: 'auto' takes the kernel
    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    T2, c2 = run()
    assert len(calls) == 5  # every payload column, both sides
    assert int(c1) == int(c2)
    for n in T1.column_names:
        np.testing.assert_array_equal(np.asarray(T1[n]), np.asarray(T2[n]))


@settings(max_examples=15, deadline=None)
@given(
    n_r=st.integers(8, 300),
    n_s=st.integers(8, 500),
    r_pay=st.integers(0, 3),
    seed=st.integers(0, 2**31 - 1),
    alg_pat=st.sampled_from([("smj", "gftr"), ("phj", "gftr"), ("phj", "gfur")]),
)
def test_join_property(n_r, n_s, r_pay, seed, alg_pat):
    """Property: for any sizes/payload counts/seed, join == oracle."""
    rng = np.random.default_rng(seed)
    alg, pattern = alg_pat
    R, S, rk, rp, sk, sp = make_tables(rng, n_r, n_s, r_pay, 1)
    expected = oracle_join(rk, rp, sk, sp)
    T, count = join(R, S, algorithm=alg, pattern=pattern, out_size=n_s)
    got = result_rows(T, count, [f"r{i}" for i in range(r_pay)], ["s0"])
    assert got == expected


def test_join_sequence_star(rng):
    n_f, n_d, N = 1000, 200, 3
    fact_cols = {"label": jnp.arange(n_f, dtype=jnp.int32)}
    fks = []
    for i in range(N):
        fact_cols[f"fk{i}"] = jnp.asarray(rng.integers(0, n_d, n_f).astype(np.int32))
        fks.append(f"fk{i}")
    fact = Table(fact_cols)
    dims, dks = [], []
    for i in range(N):
        dk = rng.permutation(n_d).astype(np.int32)
        dims.append(Table({f"k{i}": jnp.asarray(dk),
                           f"p{i}": jnp.asarray(dk * (i + 2))}))
        dks.append(f"k{i}")
    T, count = join_sequence(fact, dims, fk_cols=fks, dim_keys=dks,
                             algorithm="phj", pattern="gftr")
    assert int(count) == n_f
    lab = np.asarray(T["label"])
    for i in range(N):
        fk = np.asarray(fact_cols[f"fk{i}"])[lab]
        assert (np.asarray(T[f"p{i}"]) == fk * (i + 2)).all()


def test_phj_checked_escalates_on_duplicate_heavy_build(rng):
    """Build side with few distinct keys overflows the default blocks; the
    checked driver escalates fan-out / relies on big blocks and stays exact."""
    from repro.core import phj_join_checked, phj_overflowed

    rk = rng.integers(0, 8, 2000).astype(np.int32)
    sk = rng.integers(0, 8, 500).astype(np.int32)
    R = Table({"k": jnp.asarray(rk), "r0": jnp.arange(2000, dtype=jnp.int32)})
    S = Table({"k": jnp.asarray(sk), "s0": jnp.arange(500, dtype=jnp.int32)})
    ovf, _ = phj_overflowed(R)
    assert ovf
    expected = oracle_join(rk, [np.asarray(R["r0"])], sk, [np.asarray(S["s0"])])
    T, c = phj_join_checked(R, S, mode="mn", out_size=len(expected) + 64,
                            build_block=2048)
    assert result_rows(T, c, ["r0"], ["s0"]) == expected
