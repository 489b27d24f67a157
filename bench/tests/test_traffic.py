"""The traffic generators: every seed offers the same load in another
order, and the laws they copy hold."""
import numpy as np
import pytest

from bench.traffic import serve


def test_every_seed_offers_the_same_arrivals_in_another_order():
    d1, u1 = serve.schedule(1, 200.0, 20.0, 20_980_000)
    d2, u2 = serve.schedule(2**31 + 9, 200.0, 20.0, 20_980_000)
    assert len(d1) == len(d2) == 4000
    g1, g2 = np.diff(np.r_[0, d1]), np.diff(np.r_[0, d2])
    assert not np.array_equal(g1, g2)
    np.testing.assert_allclose(np.sort(g1), np.sort(g2))
    assert d1[-1] == pytest.approx(d2[-1]) and d1[-1] <= 20.0
    assert u1.min() >= 0 and u1.max() < 20_980_000
    assert not np.array_equal(u1, u2)


def test_zipf_ranks_follow_one_over_rank():
    from bench.traffic import cf_data
    rng = np.random.default_rng(0)
    n, count = 1000, 400_000
    ranks = cf_data.zipf_rank(rng.random(count), n, xp=np)
    assert ranks.min() >= 0 and ranks.max() < n
    h = np.sum(1.0 / np.arange(1, n + 1))
    for r in (0, 1, 9, 99):
        expect = count / ((r + 1) * h)
        assert abs(np.sum(ranks == r) - expect) < 5 * np.sqrt(expect)


def test_harmonic_matches_the_sum():
    from bench.traffic import cf_data
    m = np.array([1, 2, 63, 64, 65, 1000, 123457])
    exact = np.array([np.sum(1.0 / np.arange(1, k + 1)) for k in m])
    np.testing.assert_allclose(cf_data.harmonic(m, xp=np), exact, rtol=1e-8)


def test_host_zipf_rank_is_the_exact_inverse_at_the_user_count():
    from bench.traffic import cf_data
    n = 20_980_000
    u = np.random.default_rng(3).random(20_000)
    r = cf_data.zipf_rank(u, n, xp=np)
    below = np.where(r == 0, 0.0, cf_data.harmonic(r, xp=np))
    target = u * cf_data.harmonic(n, xp=np)
    # rank r holds the draws with H(r) <= u H(n) < H(r + 1), H(0) = 0
    assert np.all((below <= target) & (target < cf_data.harmonic(r + 1, xp=np)))
    assert np.mean(r == 0) > 0.04 and r.max() > n // 100


def test_device_data_keeps_distinct_positives_from_the_users_cluster():
    import jax
    from bench.traffic import cf_data
    tp = jax.device_get(cf_data.generate(0, 3000, 2000, clusters=4,
                                         columns=16, candidates=48))
    assert tp.shape == (3000, 16) and (tp >= 0).all()
    assert all(len(set(r)) == 16 for r in tp[:200])
    w = np.bincount(tp.ravel(), minlength=2000)
    # positives are skewed: the most drawn item is far above the mean
    assert w.max() > 20 * w.mean()


def test_device_zipf_rank_inverts_the_harmonic_law():
    import jax.numpy as jnp
    from bench.traffic import cf_data
    m = np.unique(np.r_[np.arange(1, 200), np.geomspace(200, 2e6, 400)]
                  .astype(np.int64))
    exact_h = np.cumsum(1.0 / np.arange(1, m[-1] + 1))[m - 1]
    got_h = np.asarray(cf_data.harmonic(jnp.asarray(m, jnp.int32)))
    np.testing.assert_allclose(got_h, exact_h, rtol=1e-6)
    u = np.random.default_rng(0).random(200_000).astype(np.float32)
    for n in (1, 7, 500, 195_000):
        h = np.cumsum(1.0 / np.arange(1, n + 1))
        exact = np.minimum(np.searchsorted(h, u * h[-1], side="right"), n - 1)
        r = np.asarray(cf_data.zipf_rank(jnp.asarray(u), jnp.int32(n)))
        # float32 rounding of H moves a draw by one rank at most, rarely
        assert np.max(np.abs(r - exact)) <= 1
        assert np.mean(r != exact) < 0.03


def test_first_distinct_keeps_draw_order():
    import jax.numpy as jnp
    from bench.traffic import cf_data
    items = np.random.default_rng(1).integers(0, 20, (300, 24))
    got = np.asarray(cf_data.first_distinct(jnp.asarray(items, jnp.int32), 8))
    for row, out in zip(items, got):
        seen = list(dict.fromkeys(row.tolist()))[:8]
        assert out.tolist() == seen + [-1] * (8 - len(seen))
