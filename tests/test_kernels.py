"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracle,
sweeping shapes and dtypes (hypothesis) per the repo contract."""
from __future__ import annotations

from hypothesis import given, settings, strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.histogram import histogram_pallas
from repro.kernels.radix_partition import partition_ranks_pallas
from repro.kernels.segsum import segsum_partials_pallas


@settings(max_examples=12, deadline=None)
@given(n=st.integers(1, 3000), bins=st.sampled_from([2, 7, 16, 64, 256]),
       seed=st.integers(0, 2**31 - 1))
def test_histogram_sweep(n, bins, seed):
    rng = np.random.default_rng(seed)
    d = jnp.asarray(rng.integers(0, bins, n).astype(np.int32))
    np.testing.assert_array_equal(
        np.asarray(histogram_pallas(d, bins)), np.asarray(ref.histogram(d, bins))
    )


@settings(max_examples=12, deadline=None)
@given(n=st.integers(1, 2000), bins=st.sampled_from([2, 8, 32, 128]),
       seed=st.integers(0, 2**31 - 1))
def test_partition_ranks_sweep(n, bins, seed):
    rng = np.random.default_rng(seed)
    d = jnp.asarray(rng.integers(0, bins, n).astype(np.int32))
    dest, off, sz = partition_ranks_pallas(d, bins)
    np.testing.assert_array_equal(np.asarray(dest), np.asarray(ref.partition_ranks(d, bins)))
    # applying the ranks yields a stable partition
    outs = ops.apply_partition(dest, d)
    assert bool((jnp.diff(outs[0]) >= 0).all())


def test_pad_rows_excluded_by_construction(rng):
    """Histogram/rank kernels must exclude PAD_DIGIT rows via the explicit
    mask in `digit_onehot` — any negative digit counts nowhere and gets no
    destination, however the bins are laid out."""
    from repro.kernels.common import digit_onehot, lane_onehot
    from repro.kernels.radix_partition import block_histograms_pallas

    d = np.asarray(rng.integers(0, 16, 100).astype(np.int32))
    d[::7] = -1  # explicit pad/sentinel rows inside the data
    dj = jnp.asarray(d)
    assert int(histogram_pallas(dj, 16).sum()) == int((d >= 0).sum())
    assert int(block_histograms_pallas(dj, 16).sum()) == int((d >= 0).sum())
    dest, _, sizes = partition_ranks_pallas(dj, 16)
    assert int(sizes.sum()) == int((d >= 0).sum())
    assert (np.asarray(dest)[d < 0] == -1).all()
    # the shared one-hot cores mask any negative digit, not just -1
    oh = np.asarray(digit_onehot(jnp.asarray([-5, 0, 3, -1], jnp.int32), 4))
    np.testing.assert_array_equal(oh.sum(axis=1), [0, 1, 1, 0])
    oh_t = np.asarray(lane_onehot(jnp.asarray([[-5, 0, 3, -1]], jnp.int32), 4))
    np.testing.assert_array_equal(oh_t, oh.T)


def test_interpret_resolution_env_override(monkeypatch):
    """Backend detection picks interpret off-TPU; REPRO_PALLAS_INTERPRET
    overrides it both ways."""
    from repro.kernels import common

    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    on_tpu = jax.default_backend() == "tpu"
    assert common.default_interpret() == (not on_tpu)
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert common.default_interpret() is False
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert common.default_interpret() is True
    assert common.resolve_interpret(None) is True
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "off")
    assert common.resolve_interpret(None) is False
    assert common.resolve_interpret(True) is True  # explicit flag wins


@settings(max_examples=10, deadline=None)
@given(nb=st.integers(10, 4000), npr=st.integers(10, 4000),
       seed=st.integers(0, 2**31 - 1))
def test_merge_lower_bound_sweep(nb, npr, seed):
    rng = np.random.default_rng(seed)
    b = jnp.sort(jnp.asarray(rng.integers(0, 1 << 20, nb).astype(np.int32)))
    p = jnp.sort(jnp.asarray(rng.integers(0, 1 << 20, npr).astype(np.int32)))
    lb = ops.merge_lower_bound(b, p, "auto", window_rows=256, tile=256)
    np.testing.assert_array_equal(np.asarray(lb), np.asarray(ref.lower_bound(b, p)))


def test_hash_probe_matches_ref(rng):
    from repro.core import primitives as prim
    from repro.core.hash_join import hash32, build_blocks

    nR, nS, p_bits, cap = 1500, 4000, 5, 256
    P = 1 << p_bits
    rkeys = jnp.asarray(rng.permutation(50000)[:nR].astype(np.int32))
    skeys = jnp.asarray(rng.choice(np.asarray(rkeys), nS).astype(np.int32))
    dig_r = (hash32(rkeys) & (P - 1)).astype(jnp.int32)
    dig_s = (hash32(skeys) & (P - 1)).astype(jnp.int32)
    perm_r, off_r, sz_r = prim.partition_permutation(dig_r, P)
    perm_s, off_s, sz_s = prim.partition_permutation(dig_s, P)
    kr, ks = jnp.take(rkeys, perm_r), jnp.take(skeys, perm_s)
    bkeys, _, ovf = build_blocks(kr, off_r, sz_r, cap)
    assert not bool(ovf)
    vid_p, hit_p = ops.hash_probe(bkeys, off_r, ks, off_s, sz_s, "pallas")
    vid_x, hit_x = ops.hash_probe(bkeys, off_r, ks, off_s, sz_s, "xla")
    np.testing.assert_array_equal(np.asarray(hit_p), np.asarray(hit_x))
    np.testing.assert_array_equal(
        np.asarray(jnp.where(hit_p, vid_p, -1)), np.asarray(jnp.where(hit_x, vid_x, -1))
    )
    assert bool(hit_p.all())
    assert bool((jnp.take(kr, vid_p) == ks).all())


@pytest.mark.parametrize("dtype", [np.float32, np.int32])
def test_gather_windowed_dtypes(dtype, rng):
    n = 6000
    if dtype == np.int32:
        src = jnp.asarray(rng.integers(0, (1 << 31) - 1, n).astype(dtype))
    else:
        src = jnp.asarray(rng.normal(size=n).astype(dtype))
    idx = jnp.sort(jnp.asarray(rng.integers(0, n, 3000).astype(np.int32)))
    out = ops.clustered_gather(src, idx, "auto", window_rows=512, tile=512)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(jnp.take(src, idx)))


def test_gather_unclustered_fallback(rng):
    src = jnp.asarray(rng.normal(size=4096).astype(np.float32))
    idx = jnp.asarray(rng.permutation(4096).astype(np.int32))
    out = ops.clustered_gather(src, idx, "auto", window_rows=256, tile=256)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(jnp.take(src, idx)))


def _fill_take(src, idx):
    """`primitives.gather(src, idx, fill=0)` in NumPy: the oracle."""
    src, idx = np.asarray(src), np.asarray(idx)
    ok = (idx >= 0) & (idx < src.shape[0])
    return np.where(ok, src[np.clip(idx, 0, src.shape[0] - 1)], src.dtype.type(0))


def _phj_build_ids(rng):
    """ID_R of a small PHJ-OM join: the build-side virtual IDs of its
    output, clustered within co-partitions but not monotone, -1 past the
    matches."""
    from repro.core import Table, primitives as prim
    from repro.core.hash_join import phj_join

    seen = []
    real = prim.clustered_gather

    def record(src, idx):
        seen.append(idx)
        return real(src, idx)

    R = Table({"k": jnp.asarray(rng.permutation(6000).astype(np.int32)),
               "r0": jnp.zeros((6000,), jnp.int32)})
    S = Table({"k": jnp.asarray(rng.integers(0, 9000, 5000).astype(np.int32))})
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(prim, "clustered_gather", record)
        phj_join(R, S, build_block=64, out_size=5120)
    return np.asarray(seen[0])


def _gather_case(name, rng):
    """(idx, tile) for a source of 6000 rows gathered in windows of 512."""
    if name == "probe_ids":  # monotone, -1 tail, fully invalid tiles
        idx = np.full(4096, -1, np.int32)
        idx[:2500] = np.sort(rng.choice(6000, 2500, replace=False))
        return idx, 128
    if name == "build_ids":
        return _phj_build_ids(rng), 128
    if name == "min_below_first":  # the first index is not the smallest
        idx = np.arange(1000, 1512, dtype=np.int32)
        idx[0], idx[7] = 1400, 910
        return idx, 256
    # too wide for any window: every tile spans the whole source
    return rng.permutation(6000).astype(np.int32)[:2048], 256


@pytest.mark.parametrize("dtype", [np.int32, np.float32])
@pytest.mark.parametrize("case", ["probe_ids", "build_ids", "min_below_first",
                                  "too_wide"])
def test_clustered_gather_kernel_under_jit(case, dtype, rng):
    """The kernel arm, compiled by jit, equals the fill=0 take bit for bit,
    whatever the spans; a clustered map needs one window per tile."""
    from repro.kernels.gather import window_plan

    # random words: as floats they hold NaNs, infinities and -0.0 too
    src = rng.integers(-(1 << 31), (1 << 31) - 1, 6000, dtype=np.int64)
    src = src.astype(np.int32).view(dtype)
    idx, tile = _gather_case(case, rng)
    fn = jax.jit(lambda s, i: ops.clustered_gather(s, i, "pallas",
                                                   window_rows=512, tile=tile))
    out = np.asarray(fn(jnp.asarray(src), jnp.asarray(idx)))
    np.testing.assert_array_equal(out.view(np.int32),
                                  _fill_take(src, idx).view(np.int32))
    win, n_win = (np.asarray(a) for a in window_plan(
        jnp.asarray(idx), 6000, window_rows=512, tile=tile))
    tiles = np.pad(idx, (0, -len(idx) % tile), constant_values=-1).reshape(-1, tile)
    dead = (tiles < 0).all(axis=1)
    assert ((win < 0) == dead).all() and ((n_win == 0) == dead).all()
    assert n_win.max() > 1 if case == "too_wide" else n_win.max() == 1
    if case == "probe_ids":
        assert dead.sum() >= 12  # the -1 tail fills whole tiles
    if case == "min_below_first":
        assert win[0] == 910 // 128  # the smallest index's block, not the first's


def test_clustered_gather_traces_without_values(monkeypatch):
    """The default dispatch, as on a backend that compiles Pallas, traces
    from shapes alone: nothing in it asks the device for a value (a host
    sync would fail here), and it reaches the kernel."""
    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    src = jax.ShapeDtypeStruct((1 << 14,), jnp.int32)
    idx = jax.ShapeDtypeStruct((3 << 12,), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda s, i: ops.clustered_gather(s, i))(src, idx)
    assert "pallas_call" in str(jaxpr)


@settings(max_examples=10, deadline=None)
@given(n=st.integers(1, 3000), g=st.integers(1, 100), tile=st.sampled_from([64, 256]),
       seed=st.integers(0, 2**31 - 1))
def test_segsum_partials_sweep(n, g, tile, seed):
    rng = np.random.default_rng(seed)
    keys = jnp.sort(jnp.asarray(rng.integers(0, g, n).astype(np.int32)))
    vals = jnp.asarray(rng.normal(size=n).astype(np.float32))
    pk, ps, pc = segsum_partials_pallas(keys, vals, tile=tile)
    rk, rs, rc = ref.segsum_partials(keys, vals, tile)
    np.testing.assert_array_equal(np.asarray(pk), np.asarray(rk))
    np.testing.assert_allclose(np.asarray(ps), np.asarray(rs), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(pc), np.asarray(rc))


def test_groupby_sorted_sum_end_to_end(rng):
    keys = jnp.sort(jnp.asarray(rng.integers(0, 77, 5000).astype(np.int32)))
    vals = jnp.asarray(rng.normal(size=5000).astype(np.float32))
    gk, gs, cnt = ops.groupby_sorted_sum(keys, vals, 128, "pallas")
    import collections
    exp = collections.defaultdict(float)
    for k, v in zip(np.asarray(keys), np.asarray(vals)):
        exp[int(k)] += float(v)
    got = {int(k): float(s) for k, s in zip(np.asarray(gk), np.asarray(gs)) if k != -1}
    assert int(cnt) == len(exp)
    for k in exp:
        assert abs(got[k] - exp[k]) < 1e-2
