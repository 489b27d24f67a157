"""Data pipeline contracts: host/device batch parity, explicit stable
(seed, step) mixing (no CPython hash anywhere in batch derivation), and the
device-resident dataset view consumed by the EpochExecutor."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.data import pipeline


def _ds():
    return pipeline.synth_cf_dataset(60, 90, interactions_per_user=12,
                                     num_clusters=8, seed=4)


def test_synth_distinct_draws_match_sequential_reference():
    """The bulk generator's per-user draws (repeat rejection) follow the
    same law as numpy's one-user-at-a-time ``choice(replace=False, p=1/rank)``
    loop it replaced: distinct ranks in every row, and the same first-draw
    and inclusion frequencies per rank.  With 20k rows a frequency's
    standard error is at most 0.0036, so 0.02 is over five of them."""
    pool, k, rows = 20, 5, 20_000
    got = pipeline._distinct_power_law(np.random.default_rng(1), pool, rows, k)
    w = 1.0 / np.arange(1, pool + 1)
    ref_rng = np.random.default_rng(2)
    want = np.stack([ref_rng.choice(pool, size=k, replace=False, p=w / w.sum())
                     for _ in range(rows)])
    assert got.shape == (rows, k)
    assert all(len(set(r)) == k for r in got.tolist())

    def freqs(draws):
        first = np.bincount(draws[:, 0], minlength=pool) / rows
        incl = np.bincount(draws.ravel(), minlength=pool) / rows
        return first, incl

    for a, b in zip(freqs(got), freqs(want)):
        np.testing.assert_allclose(a, b, atol=0.02, rtol=0)


def test_host_device_batch_parity():
    """cf_batch (host, eager) and cf_batch_device (jitted over the device
    dataset) produce bit-identical batches for the same (seed, step) — the
    invariant that lets the per-step loop and the scanned executor share one
    trajectory."""
    ds = _ds()
    dds = pipeline.device_cf_dataset(ds)
    dev = jax.jit(lambda s: pipeline.cf_batch_device(dds, 3, s, 16, 4))
    for step in (0, 1, 7, 1000):
        host = pipeline.cf_batch(ds, step, 16, 4, seed=3)
        got = dev(step)
        np.testing.assert_array_equal(host.user_ids, got.user_ids)
        np.testing.assert_array_equal(host.pos_ids, got.pos_ids)
        np.testing.assert_array_equal(host.hist_ids, got.hist_ids)
        np.testing.assert_array_equal(host.hist_mask, got.hist_mask)


def test_cf_batch_device_traced_step_in_scan():
    """The in-scan form: a traced step index yields the same batches as
    per-step host calls (what EpochExecutor windows rely on)."""
    ds = _ds()
    dds = pipeline.device_cf_dataset(ds)

    def body(_, step):
        b = pipeline.cf_batch_device(dds, 0, step, 8)
        return None, (b.user_ids, b.pos_ids)

    _, (users, pos) = jax.lax.scan(body, None, jnp.arange(5))
    for i in range(5):
        host = pipeline.cf_batch(ds, i, 8, seed=0)
        np.testing.assert_array_equal(host.user_ids, users[i])
        np.testing.assert_array_equal(host.pos_ids, pos[i])


def test_cf_batch_distinct_across_steps_and_seeds():
    ds = _ds()
    a = pipeline.cf_batch(ds, 0, 32, seed=0)
    b = pipeline.cf_batch(ds, 1, 32, seed=0)
    c = pipeline.cf_batch(ds, 0, 32, seed=1)
    assert not np.array_equal(a.user_ids, b.user_ids)
    assert not np.array_equal(a.user_ids, c.user_ids)


def test_cf_batch_positives_valid():
    """Every sampled positive is a real (non-padded) train item of its user,
    including users whose rows are entirely padding (fallback 0)."""
    ds = _ds()
    for step in range(4):
        b = pipeline.cf_batch(ds, step, 64, seed=9)
        users = np.asarray(b.user_ids)
        pos = np.asarray(b.pos_ids)
        rows = ds.train_pos[users]
        ok = (rows == pos[:, None]).any(axis=1)
        empty = (rows < 0).all(axis=1)
        assert (ok | (empty & (pos == 0))).all()


def test_device_dataset_weights_are_interaction_counts():
    ds = _ds()
    dds = pipeline.device_cf_dataset(ds)
    valid = ds.train_pos[ds.train_pos >= 0]
    expect = np.bincount(valid.ravel(), minlength=ds.num_items)
    np.testing.assert_array_equal(np.asarray(dds.item_weights), expect)
    assert dds.item_weights.shape == (ds.num_items,)


_SHARD_DS = _ds()
_SHARD_DDS = pipeline.device_cf_dataset(_SHARD_DS)


@settings(max_examples=25, deadline=None)
@given(batch=st.integers(1, 48), shards=st.integers(1, 9),
       seed=st.integers(0, 3), step=st.integers(0, 1000))
def test_cf_batch_shard_partitions_exactly(batch, shards, seed, step):
    """Per-shard sampling is an exact partition of the host batch at the same
    (seed, step): concatenating the shards reproduces cf_batch bit-for-bit
    (no dropped or duplicated rows), shard sizes differ by at most one, and
    uneven ``batch % shards`` remainders are spread over the low shards."""
    host = pipeline.cf_batch(_SHARD_DS, step, batch, 2, seed)
    parts = [pipeline.cf_batch_shard(_SHARD_DDS, seed, step, batch, s, shards,
                                     history_len=2)
             for s in range(shards)]
    sizes = [int(p.user_ids.shape[0]) for p in parts]
    assert sum(sizes) == batch
    assert max(sizes) - min(sizes) <= 1
    assert sorted(sizes, reverse=True) == sizes      # remainder on low shards
    cat = jax.tree.map(lambda *xs: np.concatenate([np.asarray(x) for x in xs]),
                       *parts)
    for a, b in zip(jax.tree.leaves(host), jax.tree.leaves(cat)):
        np.testing.assert_array_equal(np.asarray(a), b)


def test_lm_batch_extras_stable_mix():
    """Extras keys are derived via crc32, not salted str hash: the same name
    always yields the same stream, distinct names yield distinct streams."""
    spec = {"frames": ((2, 3, 4), jnp.float32)}
    a = pipeline.lm_batch(5, 2, 8, 50, seed=1, extras=spec)
    b = pipeline.lm_batch(5, 2, 8, 50, seed=1, extras=spec)
    np.testing.assert_array_equal(a["frames"], b["frames"])
    other = pipeline.lm_batch(5, 2, 8, 50, seed=1,
                              extras={"patches": ((2, 3, 4), jnp.float32)})
    assert not np.array_equal(a["frames"], other["patches"])


def test_lm_batch_traced_step():
    """lm_batch is scan-traceable (the LM executor samples in-window)."""
    def body(_, step):
        return None, pipeline.lm_batch(step, 2, 8, 50, seed=7)["tokens"]

    _, toks = jax.lax.scan(body, None, jnp.arange(3))
    for i in range(3):
        np.testing.assert_array_equal(
            pipeline.lm_batch(i, 2, 8, 50, seed=7)["tokens"], toks[i])
