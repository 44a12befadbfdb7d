"""Sparsifier S(.) properties: Definition 2 and Lemma 1 of §3."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import sparsifier


def test_values_are_scaled_or_zero():
    key = jax.random.PRNGKey(0)
    x = jnp.ones((4, 257))
    out = sparsifier.bernoulli_sparsify(key, x, 0.3)
    vals = np.unique(np.asarray(out))
    assert all(np.isclose(v, 0.0) or np.isclose(v, 1.0 / 0.3, rtol=1e-5)
               for v in vals)


def test_unbiasedness_statistical():
    """E[S(x)] = x (Lemma 1.i), checked by averaging many masks."""
    x = jnp.array(np.random.default_rng(0).normal(size=(64,)), jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(1), 4000)
    outs = jax.vmap(lambda k: sparsifier.bernoulli_sparsify(k, x, 0.25))(keys)
    np.testing.assert_allclose(np.asarray(outs.mean(0)), np.asarray(x),
                               atol=0.25)


def test_variance_matches_lemma1():
    """Var(S(x)) = (1/p - 1)||x||^2 (summed over coordinates)."""
    p = 0.4
    x = jnp.array(np.random.default_rng(2).normal(size=(128,)), jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(3), 8000)
    outs = np.asarray(
        jax.vmap(lambda k: sparsifier.bernoulli_sparsify(k, x, p))(keys))
    emp_var = outs.var(axis=0).sum()
    pred = float(sparsifier.sparsifier_variance(x, p))
    assert emp_var == pytest.approx(pred, rel=0.1)


def test_p_one_identity():
    x = jnp.arange(10.0)
    out = sparsifier.bernoulli_sparsify(jax.random.PRNGKey(0), x, 1.0)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


def test_fixedk_exact_count():
    x = jnp.array(np.random.default_rng(4).normal(size=(1000,)), jnp.float32)
    out = sparsifier.fixedk_sparsify(jax.random.PRNGKey(5), x, 0.2)
    assert int((np.asarray(out) != 0).sum()) == 200


def test_fixedk_unbiased_statistical():
    x = jnp.array(np.random.default_rng(6).normal(size=(50,)), jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(7), 4000)
    outs = jax.vmap(lambda k: sparsifier.fixedk_sparsify(k, x, 0.3))(keys)
    np.testing.assert_allclose(np.asarray(outs.mean(0)), np.asarray(x),
                               atol=0.25)


def test_fixedk_pack_unpack_roundtrip():
    d = 333
    x = jnp.array(np.random.default_rng(8).normal(size=(d,)), jnp.float32)
    k = sparsifier.num_kept(d, 0.25)
    idx = sparsifier.fixedk_indices(jax.random.PRNGKey(9), d, k)
    dense = sparsifier.fixedk_unpack(sparsifier.fixedk_pack(x, idx, d), idx, d)
    # kept coordinates scaled by exactly d/k, others zero
    mask = np.zeros(d, bool)
    mask[np.asarray(idx)] = True
    np.testing.assert_allclose(np.asarray(dense)[mask],
                               np.asarray(x)[mask] * (d / k), rtol=1e-6)
    assert (np.asarray(dense)[~mask] == 0).all()


def test_fixedk_indices_distinct_and_regenerable():
    idx1 = sparsifier.fixedk_indices(jax.random.PRNGKey(10), 500, 100)
    idx2 = sparsifier.fixedk_indices(jax.random.PRNGKey(10), 500, 100)
    np.testing.assert_array_equal(np.asarray(idx1), np.asarray(idx2))
    assert len(np.unique(np.asarray(idx1))) == 100


@given(d=st.integers(1, 2048), p=st.floats(0.01, 1.0))
@settings(max_examples=200, deadline=None)
def test_num_kept_properties(d, p):
    k = sparsifier.num_kept(d, p)
    assert 1 <= k <= d
    assert k >= p * d - 1e-9  # ceil


def test_num_kept_exact_ceil_sweep():
    """k == ceil(p*d) in EXACT arithmetic for every short-decimal p.

    Regression for the float-overshoot bug: 100 * 0.07 ==
    7.000000000000001 in binary, so a naive ceil returned 8 where the
    contract says ceil(0.07 * 100) = 7.
    """
    import math
    from fractions import Fraction

    ps = ["0.01", "0.02", "0.05", "0.07", "0.1", "0.125", "0.2", "0.25",
          "0.3", "1/3", "0.35", "0.5", "0.7", "0.75", "0.9", "0.99", "1.0"]
    for p_str in ps:
        p_exact = Fraction(p_str) if "/" in p_str else Fraction(p_str)
        p = float(p_exact)
        for d in range(1, 513):
            expected = max(1, min(d, math.ceil(p_exact * d)))
            assert sparsifier.num_kept(d, p) == expected, (d, p_str)


def test_num_kept_overshoot_regression():
    assert sparsifier.num_kept(100, 0.07) == 7
    assert sparsifier.num_kept(1000, 0.07) == 70
    assert sparsifier.num_kept(100, 0.29) == 29
    # beyond float precision: 1e8 * 0.07 == 7000000.000000001 and the ulp
    # there defeats decimal-rounding workarounds; exact arithmetic holds.
    assert sparsifier.num_kept(100_000_000, 0.07) == 7_000_000
    assert sparsifier.num_kept(10**12, 0.07) == 7 * 10**10


@given(p=st.sampled_from([0.1, 0.25, 0.5, 0.9]),
       seed=st.integers(0, 2**31 - 1))
@settings(max_examples=30, deadline=None)
def test_sparsify_support_subset_property(p, seed):
    """S(x) is supported on a subset of supp(x) and scales by 1/p."""
    x = jnp.array(np.random.default_rng(seed % 100).normal(size=(64,)),
                  jnp.float32)
    out = np.asarray(
        sparsifier.bernoulli_sparsify(jax.random.PRNGKey(seed), x, p))
    xs = np.asarray(x)
    nz = out != 0
    np.testing.assert_allclose(out[nz], xs[nz] / p, rtol=1e-5)


def _topk_set(idx, d):
    mask = np.zeros(d, bool)
    mask[np.asarray(idx)] = True
    return mask


@pytest.mark.parametrize("d,k", [(1, 1), (2, 1), (2, 2), (7, 3), (127, 1),
                                 (127, 127), (333, 83), (1000, 1000),
                                 (4097, 819), (65539, 13108)])
def test_fixedk_mask_is_the_topk_set(d, k):
    """fixedk_mask holds exactly fixedk_indices' set: the same scores,
    ranked by counting (k = 1, k = d, d not a multiple of 128)."""
    for seed in range(3):
        key = jax.random.PRNGKey(seed)
        mask = np.asarray(sparsifier.fixedk_mask(key, d, k))
        assert mask.dtype == bool and mask.shape == (d,)
        np.testing.assert_array_equal(
            mask, _topk_set(sparsifier.fixedk_indices(key, d, k), d))


def test_score_keys_order_the_uniform_scores():
    """jax.random.uniform's float32 scores are (bits >> 9) / 2**23 bit for
    bit, so ranking the integer keys ranks the scores."""
    key = jax.random.PRNGKey(11)
    u = np.asarray(jax.random.uniform(key, (1 << 16,)))
    bits = np.asarray(jax.random.bits(key, (1 << 16,), jnp.uint32))
    keys = bits >> (32 - sparsifier.SCORE_BITS)
    np.testing.assert_array_equal(
        u, keys.astype(np.float32) / 2 ** sparsifier.SCORE_BITS)


def test_fixedk_mask_traced_k():
    """k as a traced int32 scalar: one program serves every k."""
    d = 1031
    key = jax.random.PRNGKey(12)
    draw = jax.jit(lambda k: sparsifier.fixedk_mask(key, d, k))
    for k in (1, 2, 200, 515, 1030, 1031):
        np.testing.assert_array_equal(
            np.asarray(draw(jnp.int32(k))),
            _topk_set(sparsifier.fixedk_indices(key, d, k), d))
    assert draw._cache_size() == 1


@pytest.mark.parametrize("d", [1, 5, 100, 1031, 5003])
def test_topk_mask_tie_heavy_keys(d):
    """Keys in [0, 8): most elements tie at the threshold, and lax.top_k
    keeps the lowest-index ones; the counting search must too."""
    rng = np.random.default_rng(d)
    m = jnp.asarray(rng.integers(0, 8, d), jnp.int32)
    for k in sorted({1, max(1, d // 3), max(1, d // 2), d - 1 or 1, d}):
        np.testing.assert_array_equal(
            np.asarray(sparsifier.topk_mask(m, k, 3)),
            _topk_set(jax.lax.top_k(m, k)[1], d), err_msg=f"k={k}")


def _top_k_of(m, k):
    return _topk_set(jax.lax.top_k(m, k)[1], m.shape[-1])


def _window_holds(m, k, bits):
    """Whether ``search_window``'s window holds the k-th largest key:
    count(m >= lo) >= k > count(m >= lo + width)."""
    lo, width = sparsifier.search_window(m.shape[-1], k, bits)
    m = np.asarray(m)
    return (m >= int(lo)).sum() >= k > (m >= int(lo) + width).sum()


@pytest.mark.parametrize("d,k", [(65539, 13108), (100000, 1),
                                 (100000, 20000), (100000, 50000),
                                 (250007, 99999), (250007, 250006)])
def test_topk_mask_windowed_uniform_keys(d, k):
    """Uniform 23-bit keys: the window is narrower than the key range
    and holds the threshold, and the mask is lax.top_k's set, for a
    static and for a traced k."""
    assert sparsifier.search_window(d, k, 23)[1] < 1 << 23
    draw = jax.jit(lambda m, k: sparsifier.topk_mask(m, k, 23))
    for seed in range(2):
        m = sparsifier.score_keys(jax.random.PRNGKey(seed), d)
        assert _window_holds(m, k, 23)
        want = _top_k_of(m, k)
        np.testing.assert_array_equal(
            np.asarray(sparsifier.topk_mask(m, k, 23)), want)
        np.testing.assert_array_equal(np.asarray(draw(m, jnp.int32(k))), want)


def _missing_keys(d):
    """23-bit keys that miss the window: bunched far below the expected
    quantile, bunched at the top, all equal, and tie-heavy (8 values
    1024 apart)."""
    rng = np.random.default_rng(d)
    return {"low": rng.integers(0, 1000, d),
            "top": rng.integers((1 << 23) - 100, 1 << 23, d),
            "equal": np.full(d, 5),
            "ties": rng.integers(0, 8, d) << 10}


@pytest.mark.parametrize("name", ["low", "top", "equal", "ties"])
def test_topk_mask_fallback_when_window_misses(name):
    """Keys the window misses for most k: the whole-range fallback still
    gives lax.top_k's set, ties at the threshold to the lowest indices."""
    d = 100000
    m = jnp.asarray(_missing_keys(d)[name], jnp.int32)
    ks = (1, 20000, 50000, d - 1, d)
    assert sum(not _window_holds(m, k, 23) for k in ks) >= 2
    for k in ks:
        np.testing.assert_array_equal(
            np.asarray(sparsifier.topk_mask(m, k, 23)), _top_k_of(m, k),
            err_msg=f"k={k}")


def test_topk_mask_batched_rows():
    """(R, d) keys: each row ranked as alone, with one row that misses
    the window, so the fallback runs for all, and with none."""
    d, k = 100000, 20000
    rows = jnp.stack([sparsifier.score_keys(jax.random.PRNGKey(s), d)
                      for s in range(3)])
    draw = jax.jit(lambda m: sparsifier.topk_mask(m, k, 23))
    missed = rows.at[1].set(jnp.asarray(_missing_keys(d)["low"], jnp.int32))
    for keys in (rows, missed):
        got = np.asarray(draw(keys))
        for i in range(3):
            np.testing.assert_array_equal(got[i], _top_k_of(keys[i], k))
    assert not _window_holds(missed[1], k, 23)
    hlo = draw.lower(rows).compile().as_text()
    assert hlo.count(" conditional(") == 1


def test_search_window_of_randk():
    """The chatglm3 random-k draw: d = 475,034,624 keys, k = d/5. The
    window is 8**4 key values, 4 passes of 8 ways (the whole range
    takes 8), and holds the (1 - k/d) quantile with at least 12 σ on
    each side."""
    d, k = 475_034_624, 95_006_925
    lo, width = sparsifier.search_window(d, k, 23)
    lo = int(lo)
    ways = 1 << sparsifier.SEARCH_BITS
    assert (ways, width) == (8, 8 ** 4)
    quantile = 2 ** 23 * (1 - k / d)
    sigma = (k * (d - k) / d) ** 0.5 * 2 ** 23 / d
    assert lo + 12 * sigma <= quantile <= lo + width - 12 * sigma
    # a traced k takes the widest σ (k = d/2): one pass more
    assert sparsifier.search_window(d, jnp.int32(k), 23)[1] == 8 ** 5
    # where the window would cover the keys' range, it is the range
    assert sparsifier.search_window(5003, 1667, 3) == (0, 8)


@pytest.mark.parametrize("d,k", [(1, 1), (127, 1), (333, 83), (4097, 819)])
def test_mask_indices_is_sorted_fixedk_indices(d, k):
    """The wire's index list, a counted mask compacted, is fixedk_indices'
    set in ascending order."""
    for seed in range(3):
        key = jax.random.PRNGKey(seed)
        np.testing.assert_array_equal(
            np.asarray(sparsifier.mask_indices(
                sparsifier.fixedk_mask(key, d, k), k)),
            np.sort(np.asarray(sparsifier.fixedk_indices(key, d, k))))


@pytest.mark.parametrize("d", [1, 5, 100, 1031, 5003])
def test_mask_indices_tie_heavy_keys(d):
    """Keys in [0, 8): the compacted list is lax.top_k's set, ties at the
    threshold to the lowest indices, in ascending order."""
    rng = np.random.default_rng(d)
    m = jnp.asarray(rng.integers(0, 8, d), jnp.int32)
    for k in sorted({1, max(1, d // 3), max(1, d // 2), d - 1 or 1, d}):
        np.testing.assert_array_equal(
            np.asarray(sparsifier.mask_indices(
                sparsifier.topk_mask(m, k, 3), k)),
            np.sort(np.asarray(jax.lax.top_k(m, k)[1])), err_msg=f"k={k}")


def test_mask_indices_traced_k():
    """A mask drawn for a traced k, compacted to a fixed length: one
    program lists every k's set in ascending order and fills the rest of
    the list with d."""
    d, size = 1031, 600
    key = jax.random.PRNGKey(13)
    draw = jax.jit(lambda k: sparsifier.mask_indices(
        sparsifier.fixedk_mask(key, d, k), size))
    for k in (1, 2, 200, 515, 599, 600):
        got = np.asarray(draw(jnp.int32(k)))
        np.testing.assert_array_equal(
            got[:k], np.sort(np.asarray(sparsifier.fixedk_indices(key, d, k))))
        assert (got[k:] == d).all(), k
    assert draw._cache_size() == 1
