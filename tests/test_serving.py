"""BatchingRecommender (launch/server.py): warmup/no-retrace contract,
request coalescing, batched-vs-direct parity, and online refresh_from."""
import bisect
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import mf, retrieval
from repro.launch.server import (QUEUE_WAIT_EDGES_S, BatchingRecommender,
                                 histogram_quantile)

USERS, ITEMS, DIM, K = 64, 200, 16, 10


def _cfg():
    return mf.MFConfig(num_users=USERS, num_items=ITEMS, emb_dim=DIM,
                       num_negatives=8, lr=0.05)


def _state(seed=0):
    return mf.init_mf(jax.random.PRNGKey(seed), _cfg())


def _index(state, tile_rows=32):
    return retrieval.build_retrieval_index(state.params.item_table,
                                           tile_rows=tile_rows)


def _direct(state, uid, *, index=None, expand_tiles=None, excl=None):
    uids = jnp.asarray([uid], jnp.int32)
    e = None if excl is None else excl[uids]
    if index is not None:
        out = retrieval.topk_pruned(state.params, uids, K, index,
                                    expand_tiles=expand_tiles,
                                    exclude_mask=e)
    else:
        out = mf.topk_all_items(state.params, uids, K, exclude_mask=e)
    return set(np.asarray(out)[0].tolist())


@pytest.mark.parametrize("pruner", ["exact", "tile"])
def test_warmup_compiles_once_and_serving_never_retraces(pruner):
    """Cold-start is paid at construction: exactly one trace, and neither
    repeated requests nor different fill levels retrace (every device call
    is padded to the one compiled max_batch shape)."""
    state = _state()
    index = _index(state) if pruner == "tile" else None
    with BatchingRecommender(state, K, pruner=pruner, index=index,
                             expand_tiles=3, max_batch=8,
                             max_wait_ms=1.0) as server:
        assert server.trace_count == 1           # warmup traced + compiled
        for uid in (0, 5, 9):
            server.recommend(uid)
        server.recommend_many(np.arange(20))     # 3 calls, padded last chunk
        assert server.trace_count == 1           # second call did not retrace


@pytest.mark.parametrize("pruner", ["exact", "tile"])
def test_batched_results_match_direct_per_user(pruner):
    """Coalescing/padding must be invisible: every user's answer equals the
    direct single-user computation."""
    state = _state()
    index = _index(state) if pruner == "tile" else None
    kw = dict(index=index, expand_tiles=index.num_tiles) \
        if pruner == "tile" else {}
    with BatchingRecommender(state, K, pruner=pruner, index=index,
                             expand_tiles=(index.num_tiles if index else 8),
                             max_batch=8, max_wait_ms=1.0) as server:
        uids = [0, 3, 7, 11, 63]
        got = server.recommend_many(uids)
        assert got.shape == (5, K)
        for uid, row in zip(uids, got):
            want = _direct(state, uid, index=index,
                           expand_tiles=kw.get("expand_tiles"))
            assert set(row.tolist()) == want


def test_concurrent_requests_are_coalesced():
    """N concurrent single-user requests land in far fewer device calls
    (the whole point of the queue), and every caller still gets the right
    answer."""
    state = _state()
    server = BatchingRecommender(state, K, max_batch=8, max_wait_ms=50.0)
    n, results = 32, {}
    lock = threading.Lock()

    def client(uid):
        out = server.recommend(uid)
        with lock:
            results[uid] = out

    threads = [threading.Thread(target=client, args=(uid,))
               for uid in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stats = server.stats
    server.stop()
    assert stats["requests_served"] == n
    assert stats["device_calls"] < n             # coalescing happened
    assert stats["traces"] == 1                  # still the one program
    for uid in range(n):
        assert set(results[uid].tolist()) == _direct(state, uid)


def test_refresh_from_swaps_tables_without_retrace():
    """refresh_from re-points the compiled program at new device tables: the
    answers change to the new state's, the trace count does not."""
    s1, s2 = _state(0), _state(1)
    index = _index(s1)
    with BatchingRecommender(s1, K, pruner="tile", index=index,
                             expand_tiles=index.num_tiles, max_batch=4,
                             max_wait_ms=1.0) as server:
        before = set(server.recommend(7).tolist())
        assert before == _direct(s1, 7, index=index,
                                 expand_tiles=index.num_tiles)
        server.refresh_from(s2)
        after = set(server.recommend(7).tolist())
        assert server.trace_count == 1
        # centroids were re-derived from s2's table under the SAME partition
        want_index = retrieval.refresh_index(index, s2.params.item_table)
        assert after == _direct(s2, 7, index=want_index,
                                expand_tiles=index.num_tiles)
        assert after != before                   # independent tables moved


def test_exclude_mask_filters_served_results():
    state = _state()
    r = np.random.default_rng(0)
    excl = jnp.asarray(r.integers(0, 2, (USERS, ITEMS)).astype(bool))
    with BatchingRecommender(state, K, max_batch=4, max_wait_ms=1.0,
                             exclude_mask=excl) as server:
        for uid in (2, 40):
            got = server.recommend(uid)
            assert not np.asarray(excl)[uid][got].any()
            assert set(got.tolist()) == _direct(state, uid, excl=excl)


def test_lazy_warmup_traces_on_first_call():
    state = _state()
    with BatchingRecommender(state, K, max_batch=4, max_wait_ms=1.0,
                             warmup=False) as server:
        assert server.trace_count == 0
        server.recommend(1)
        assert server.trace_count == 1
        server.recommend(2)
        assert server.trace_count == 1


def test_constructor_validates_args():
    state = _state()
    with pytest.raises(ValueError):
        BatchingRecommender(state, K, pruner="annoy")
    with pytest.raises(ValueError):
        BatchingRecommender(state, K, pruner="tile")   # tile needs an index


def test_queue_wait_counts_every_queued_request():
    """Each request that came through the queue is counted once in the
    queue-wait histogram, with a positive sum of waits."""
    state = _state()
    with BatchingRecommender(state, K, max_batch=8,
                             max_wait_ms=5.0) as server:
        before = server.stats
        threads = [threading.Thread(target=server.recommend, args=(uid,))
                   for uid in range(24)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in threads)
        stats = server.stats
    assert stats["queue_wait_count"] == stats["requests_served"] == 24
    assert sum(stats["queue_wait_hist"]) == 24
    assert len(stats["queue_wait_hist"]) == len(QUEUE_WAIT_EDGES_S) + 1
    assert stats["queue_wait_sum_s"] > 0
    assert before["queue_wait_count"] == 0
    for key in ("device_calls", "requests_served", "traces", "status",
                "refreshes", "refresh_failures", "stale_refreshes",
                "last_refresh_error"):
        assert key in stats                       # the existing keys stay


def test_queue_wait_covers_a_call_in_flight():
    """Requests that queue behind a device call in flight wait for it and
    then for the batching deadline: the histogram's median is at least
    ``max_wait_ms``."""
    state = _state()
    max_wait_ms = 20.0
    with BatchingRecommender(state, K, max_batch=32,
                             max_wait_ms=max_wait_ms) as server:
        fast = server._fn
        started = threading.Event()

        def slow(*args):
            started.set()
            time.sleep(0.2)
            return fast(*args)

        server._fn = slow
        first = threading.Thread(target=server.recommend, args=(0,))
        first.start()
        assert started.wait(timeout=30)          # the first call is in flight
        queued = [threading.Thread(target=server.recommend, args=(uid,))
                  for uid in range(1, 16)]
        for t in queued:
            t.start()
        for t in [first] + queued:
            t.join(timeout=30)
        stats = server.stats
    assert stats["queue_wait_count"] == 16
    median_s = histogram_quantile(stats["queue_wait_hist"], 0.5)
    assert median_s >= max_wait_ms / 1e3
    assert stats["queue_wait_sum_s"] / 16 >= max_wait_ms / 1e3


@pytest.mark.parametrize("waits_s,q,lo,hi", [
    ([0.11] * 9, 0.5, 0.11 / 1.1, 0.11 * 1.1),
    ([1e-6, 1e-6, 3.0], 0.5, 0, QUEUE_WAIT_EDGES_S[0]),
    ([500.0], 0.99, QUEUE_WAIT_EDGES_S[-1], QUEUE_WAIT_EDGES_S[-1]),
])
def test_histogram_quantile_lies_in_the_bin_of_the_wait(waits_s, q, lo, hi):
    counts = [0] * (len(QUEUE_WAIT_EDGES_S) + 1)
    for w in waits_s:
        counts[bisect.bisect_right(QUEUE_WAIT_EDGES_S, w)] += 1
    assert lo <= histogram_quantile(counts, q) <= hi
    assert histogram_quantile([0] * len(counts), q) is None
    ratios = np.diff(np.log(QUEUE_WAIT_EDGES_S))
    assert np.all(ratios <= np.log(1.1))
