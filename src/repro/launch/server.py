"""Request-batching recommendation server: coalesce concurrent single-user
requests into one (B, ·) device call.

Serving a CF model one request at a time wastes the device exactly the way
§3.1 says per-step host round-trips waste training: every request pays a
Python->XLA dispatch and an under-filled matmul.  The
:class:`BatchingRecommender` puts a small queue in front of the device:

  * the worker blocks for the first request, then drains the queue until
    ``max_batch`` requests are coalesced or ``max_wait_ms`` has elapsed
    since the first one (the latency deadline bounds the wait a lone
    request can suffer);
  * every device call is padded to exactly ``max_batch`` rows, so there is
    ONE compiled program regardless of fill level — no shape-driven
    retraces in steady state (asserted by the trace counter);
  * each queued request's wait, from its enqueue until its batch closes,
    goes into a fixed log-spaced histogram (:data:`QUEUE_WAIT_EDGES_S`) that
    :attr:`BatchingRecommender.stats` exposes with its count and sum, so
    any percentile over an interval is read from two snapshots
    (:func:`histogram_quantile`);
  * the compiled program takes the embedding tables (and the retrieval
    index) as *arguments*, not closed-over constants, so
    :meth:`refresh_from` swaps in an online trainer's updated ``MFState``
    between calls without retracing or copying through the host — the
    tables the trainer donated window-to-window are the tables served.

Construction warms the path up front (trace + compile on a dummy batch), so
the first real request pays serving latency, not compilation latency.
"""
from __future__ import annotations

import bisect
import queue
import threading
import time
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import tracing
from repro.analysis.sanitize import TraceCounter
from repro.core import mf
from repro.core import retrieval as rtv
from repro.optim import quantization as qz


#: Bin edges (s) of the queue-wait histogram: 156 log-spaced edges from
#: 50 us to 120 s, a ratio of 1.0994 between neighbours.  Bin i holds waits
#: in [edges[i-1], edges[i]); bin 0 those under the first edge, the last bin
#: those of the last edge or more.
QUEUE_WAIT_EDGES_S = tuple(float(e) for e in np.geomspace(50e-6, 120.0, 156))


def histogram_quantile(counts, q: float,
                       edges=QUEUE_WAIT_EDGES_S) -> Optional[float]:
    """The ``q`` quantile (0..1) of a histogram over ``edges``, such as the
    difference of two ``stats["queue_wait_hist"]`` snapshots: interpolated
    geometrically inside the bin that holds it, clamped to the first and
    last edge.  None for an empty histogram."""
    total = sum(counts)
    if total <= 0:
        return None
    rank, seen = q * total, 0
    for i, n in enumerate(counts):
        if n and seen + n >= rank:
            if i == 0:
                return edges[0]
            if i == len(edges):
                return edges[-1]
            frac = (rank - seen) / n
            return edges[i - 1] * (edges[i] / edges[i - 1]) ** frac
        seen += n
    return edges[-1]


class _Request(NamedTuple):
    user_id: int
    event: threading.Event
    result: list           # single-slot box the worker fills
    enqueued: float        # time.perf_counter() at the enqueue


class BatchingRecommender:
    """Batched top-k serving over device-resident MF tables.

    ``pruner="exact"`` serves through the chunked ``mf.topk_all_items``;
    ``pruner="tile"`` serves through ``retrieval.topk_pruned`` with the
    given ``index`` and ``expand_tiles`` budget.  ``exclude_mask`` (U, I)
    bool masks each user's training positives (optional — at production
    catalog scale callers pass None and post-filter).
    """

    def __init__(self, state: mf.MFState, k: int, *,
                 pruner: str = "exact",
                 index: Optional[rtv.RetrievalIndex] = None,
                 expand_tiles: int = 8,
                 max_batch: int = 32, max_wait_ms: float = 2.0,
                 similarity: str = "cosine",
                 item_chunk: Optional[int] = None,
                 exclude_mask: Optional[jax.Array] = None,
                 refresh_centroids: bool = True,
                 warmup: bool = True,
                 log: Optional[Callable[[str], None]] = None):
        if pruner not in ("exact", "tile"):
            raise ValueError(f"pruner must be 'exact' or 'tile', got {pruner!r}")
        if pruner == "tile" and index is None:
            raise ValueError("pruner='tile' requires a RetrievalIndex "
                             "(retrieval.build_retrieval_index)")
        self.k = int(k)
        self.pruner = pruner
        self.max_batch = max(int(max_batch), 1)
        self.max_wait_ms = float(max_wait_ms)
        self._similarity = similarity
        self._refresh_centroids = refresh_centroids
        self._exclude_mask = exclude_mask
        # One padded shape -> ONE trace, ever: the shared retrace detector
        # (repro.analysis) replaces PR 6's ad-hoc counter and arms a hard
        # budget — any steady-state retrace is a bug, not a slowdown.
        self.trace_counter = TraceCounter("batching_recommender", budget=1)
        self._device_calls = 0
        self._requests_served = 0
        self._counts_lock = threading.Lock()
        self._wait_hist = [0] * (len(QUEUE_WAIT_EDGES_S) + 1)
        self._wait_sum_s = 0.0
        self._log = log or (lambda *_: None)
        # degraded-serving health: a failed refresh keeps the previous
        # snapshot live and is *counted*, never swallowed silently
        self._refreshes = 0
        self._refresh_failures = 0
        self._stale_refreshes = 0
        self._last_refresh_error: Optional[str] = None

        def _recommend(params: mf.MFParams, index: Optional[rtv.RetrievalIndex],
                       user_ids: jax.Array) -> jax.Array:
            excl = (None if exclude_mask is None
                    else exclude_mask[user_ids])
            if pruner == "tile":
                return rtv.topk_pruned(params, user_ids, k, index,
                                       expand_tiles=expand_tiles,
                                       similarity=similarity,
                                       exclude_mask=excl)
            return mf.topk_all_items(params, user_ids, k,
                                     similarity=similarity,
                                     item_chunk=item_chunk,
                                     exclude_mask=excl)

        self._fn = jax.jit(self.trace_counter.wrap(_recommend))
        self._params = state.params
        # the compiled program is shape/dtype/layout-keyed: a refresh that
        # changed any (including an fp32 <-> int8 table-format swap) would
        # retrace (or serve garbage), so pin the leaf-level spec now and
        # reject non-conforming refreshes instead of degrading silently
        self._table_specs = qz.table_spec(
            (state.params.user_table, state.params.item_table))
        self._index = (rtv.refresh_index(index, state.params.item_table,
                                         similarity=similarity)
                       if (index is not None and refresh_centroids)
                       else index)

        self._queue: queue.Queue = queue.Queue()
        self._running = True
        self._worker = threading.Thread(target=self._serve_loop, daemon=True)
        if warmup:
            self.warmup()
        self._worker.start()

    # -- device path -------------------------------------------------------

    def _call(self, user_ids) -> np.ndarray:
        """One device call over up to ``max_batch`` host user ids, padded
        to ``max_batch`` rows; returns the (max_batch, k) host answer."""
        with tracing.span(tracing.SERVE_DISPATCH):
            padded = np.zeros(self.max_batch, np.int32)
            padded[:len(user_ids)] = user_ids
            out = self._fn(self._params, self._index, jnp.asarray(padded))
        self._device_calls += 1
        self.trace_counter.check()      # steady-state retrace = hard failure
        with tracing.span(tracing.SERVE_READBACK):
            return np.asarray(jax.block_until_ready(out))

    def warmup(self) -> float:
        """Trace + compile the serving path on a dummy full batch; returns
        the wall seconds spent, which the first real request then does NOT
        pay (tests assert the second call does not retrace)."""
        t0 = time.perf_counter()
        self._call(())
        return time.perf_counter() - t0

    @property
    def trace_count(self) -> int:
        return self.trace_counter.count

    @property
    def stats(self) -> dict:
        """Counters since construction.  ``queue_wait_*`` cover requests
        that came through the queue (:meth:`recommend`): their count, the
        sum of their waits in seconds, and the histogram of the waits over
        :data:`QUEUE_WAIT_EDGES_S`."""
        with self._counts_lock:
            counts = {"device_calls": self._device_calls,
                      "requests_served": self._requests_served,
                      "queue_wait_count": sum(self._wait_hist),
                      "queue_wait_sum_s": self._wait_sum_s,
                      "queue_wait_hist": list(self._wait_hist)}
        return {**counts, "traces": self.trace_counter.count, **self.health}

    @property
    def health(self) -> dict:
        """Serving health/staleness status.  ``degraded`` means the last
        refresh(es) failed and requests are served from the previous good
        snapshot; the status recovers on the next good refresh."""
        return {"status": "degraded" if self._stale_refreshes else "ok",
                "refreshes": self._refreshes,
                "refresh_failures": self._refresh_failures,
                "stale_refreshes": self._stale_refreshes,
                "last_refresh_error": self._last_refresh_error}

    def recommend_many(self, user_ids) -> np.ndarray:
        """Synchronous batched entry point (bench/offline use): pads the
        request rows to ``max_batch`` (one compiled shape) and slices the
        answer back out.  Batches larger than ``max_batch`` are split."""
        ids = np.asarray(user_ids, np.int32).reshape(-1)
        outs = []
        for s in range(0, ids.size, self.max_batch):
            chunk = ids[s:s + self.max_batch]
            outs.append(self._call(chunk)[:chunk.size])
        with self._counts_lock:
            self._requests_served += ids.size
        return np.concatenate(outs, axis=0)

    # -- queue front-end ---------------------------------------------------

    def recommend(self, user_id: int, timeout: Optional[float] = 10.0
                  ) -> np.ndarray:
        """Single-user entry point: enqueue and wait.  Concurrent callers
        are coalesced by the worker into one device call."""
        req = _Request(int(user_id), threading.Event(), [None],
                       time.perf_counter())
        self._queue.put(req)
        if not req.event.wait(timeout):
            raise TimeoutError(f"recommend({user_id}) timed out")
        res = req.result[0]
        if isinstance(res, BaseException):
            raise res
        return res

    def _serve_loop(self) -> None:
        stopping = False
        while not stopping:
            req = self._queue.get()
            if req is None:
                return
            with tracing.span(tracing.SERVE_COLLECT):
                batch = [req]
                deadline = time.monotonic() + self.max_wait_ms / 1e3
                while len(batch) < self.max_batch:
                    wait = deadline - time.monotonic()
                    if wait <= 0:
                        break
                    try:
                        nxt = self._queue.get(timeout=wait)
                    except queue.Empty:
                        break
                    if nxt is None:
                        stopping = True
                        break
                    batch.append(nxt)
                closed = time.perf_counter()
            self._flush(batch, closed)

    def _flush(self, batch: list, closed: float) -> None:
        """Serve one batch closed at ``closed`` (``time.perf_counter``)
        and count each request's wait from its enqueue until then."""
        try:
            out = self._call([r.user_id for r in batch])
            answers = list(out[:len(batch)])
        except Exception as e:  # noqa: BLE001 — surfaced to the waiters
            answers = [e] * len(batch)
        with tracing.span(tracing.SERVE_FANOUT):
            waits = [closed - r.enqueued for r in batch]
            with self._counts_lock:       # counted before any caller wakes
                self._requests_served += len(batch)
                for w in waits:
                    self._wait_hist[bisect.bisect_right(QUEUE_WAIT_EDGES_S,
                                                        w)] += 1
                self._wait_sum_s += sum(waits)
            for r, a in zip(batch, answers):
                r.result[0] = a
                r.event.set()

    # -- online refresh ----------------------------------------------------

    def _validate_refresh(self, state: mf.MFState) -> None:
        params = state.params
        got = qz.table_spec((params.user_table, params.item_table))
        if got != self._table_specs:
            raise ValueError(
                f"refresh tables have shape/dtype/layout {got[1]} "
                f"({got[0]}), the serving program was compiled for "
                f"{self._table_specs[1]} ({self._table_specs[0]}) — "
                "refusing the swap (it would retrace or serve garbage)")

    def refresh_from(self, state: mf.MFState, *,
                     on_error: str = "degrade") -> bool:
        """Swap in a (newly trained) ``MFState``'s tables.

        The jitted program takes the tables as arguments, so this is a
        reference swap of device buffers — no host round-trip, no retrace
        (same shapes/dtypes hit the same executable).  With a tile pruner
        the centroids are re-derived from the live table on device
        (``refresh_index``); the member partition is kept, so every
        compiled program stays valid.

        A failed refresh (malformed state, index refresh error) does NOT
        take serving down: with ``on_error="degrade"`` (the default) the
        previous snapshot stays live, the failure is logged + counted in
        :attr:`health`, and the status recovers on the next good refresh;
        ``on_error="raise"`` propagates instead (strict callers/tests).
        Returns True when the swap happened.
        """
        if on_error not in ("degrade", "raise"):
            raise ValueError(f"on_error must be 'degrade' or 'raise', "
                             f"got {on_error!r}")
        try:
            self._validate_refresh(state)
            new_index = (rtv.refresh_index(self._index,
                                           state.params.item_table,
                                           similarity=self._similarity)
                         if (self._index is not None
                             and self._refresh_centroids)
                         else self._index)
        except Exception as e:  # noqa: BLE001 — degraded serving, by design
            if on_error == "raise":
                raise
            self._refresh_failures += 1
            self._stale_refreshes += 1
            self._last_refresh_error = f"{type(e).__name__}: {e}"
            self._log(f"[serve] refresh failed ({self._last_refresh_error});"
                      " serving the previous snapshot "
                      f"(stale x{self._stale_refreshes})")
            return False
        self._params = state.params
        self._index = new_index
        self._refreshes += 1
        self._stale_refreshes = 0
        self._last_refresh_error = None
        return True

    def stop(self) -> None:
        if self._running:
            self._running = False
            self._queue.put(None)
            self._worker.join(timeout=5)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()
