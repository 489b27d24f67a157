"""Serving traffic: open-loop single-user top-k requests against the
program's ``BatchingRecommender`` (``launch/server.py``) over int8 or fp32
tables made from the seed.

Arrivals: ``rate_per_s`` x ``--seconds`` requests, Poisson arrivals
conditioned on that count (sorted uniform times).  The set of gaps between
arrivals is drawn once from a fixed stream and put in another order by each
seed, so every seed offers the same load.  Users: Zipf(1) ranks over all
users (P(rank r) proportional to 1/(r+1), the law of
``cf_data.zipf_rank``), mapped to ids by a seed-drawn permutation
``(a * rank + b) mod U``.
Each request is timed from when it was due to when its answer came back;
one that fails or never comes counts as missing (its latency is the wait
limit).  The generator's lateness is reported on an earlier line.

Once the window has closed, a sample of ``check_requests`` answered
requests, drawn from the seed, is compared with the exact top-k of the
plain reference (``bench/reference.topk_gap``).
"""
from __future__ import annotations

import concurrent.futures
import math
import threading
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness, reference
from bench.traffic import cf_data, tables

GAP_STREAM = 20230414            # fixed stream of the arrival gaps
WAIT_AFTER_CLOSE_S = 60.0        # how long an answer may come after the close


def schedule(seed: int, rate: float, seconds: float, num_users: int):
    """(due times in seconds from the window's start, user ids)."""
    count = max(int(round(rate * seconds)), 1)
    base = np.random.default_rng(GAP_STREAM)
    times = np.sort(base.random(count)) * seconds
    gaps = np.diff(np.concatenate([[0.0], times]))
    rng = np.random.default_rng(seed)
    due = np.cumsum(rng.permutation(gaps))
    ranks = cf_data.zipf_rank(rng.random(count), num_users,
                              xp=np).astype(np.int64)
    while True:
        a = int(rng.integers(1, num_users))
        if math.gcd(a, num_users) == 1:
            break
    b = int(rng.integers(0, num_users))
    users = (a * ranks + b) % num_users
    return due, users.astype(np.int64)


def make_tables(config: dict, seed: int):
    """(user table, item table) in the configuration's format."""
    from repro.optim import quantization as qz
    ku, ki, _ = tables.keys(jax.random.PRNGKey(seed))
    k, std = config["emb_dim"], config["init_std"]
    out = []
    for key, rows in ((ku, config["num_users"]), (ki, config["num_items"])):
        if config["table_format"] == "int8":
            out.append(qz.QuantizedTable(*tables.int8_table(key, rows, k,
                                                            std)))
        else:
            out.append(tables.normal_table(key, rows, k, std))
    return tuple(out)


def reference_rows(config: dict, seed: int, users: np.ndarray):
    """(rows of ``users``, item table) drawn again from the seed, as stored:
    the int8 payload of an int8 table (its scales cancel in a cosine), or
    the fp32 table."""
    if config["similarity"] != "cosine":
        raise harness.BenchError("the serving reference scores by cosine")
    ku, ki, _ = tables.keys(jax.random.PRNGKey(seed))
    k, std = config["emb_dim"], config["init_std"]
    if config["table_format"] == "int8":
        def draw(key, rows):
            return tables.int8_rows(key, rows, k, std)[0]
    else:
        def draw(key, rows):
            return tables.normal_table(key, rows, k, std)
    table = draw(ku, config["num_users"])
    rows = table[jnp.asarray(users, jnp.int32)]
    del table
    return rows, draw(ki, config["num_items"])


class OpenLoop:
    """Issues the schedule from one generator thread into a pool of client
    threads, each calling ``recommend`` and recording when its answer
    came."""

    def __init__(self, server, due, users, threads: int, timeout: float):
        self.server = server
        self.due, self.users = due, users
        self.done = np.full(len(due), np.nan)
        self.late = np.zeros(len(due))
        self.answers: list = [None] * len(due)
        self.errors = 0
        self._lock = threading.Lock()
        self._pool = concurrent.futures.ThreadPoolExecutor(threads)
        self._timeout = timeout

    def _client(self, i: int, t0: float) -> None:
        try:
            with harness.span("request"):
                ans = self.server.recommend(int(self.users[i]),
                                            timeout=self._timeout)
            self.done[i] = time.perf_counter() - t0
            self.answers[i] = np.asarray(ans)
        except Exception:  # noqa: BLE001 — a failed request is counted
            with self._lock:
                self.errors += 1

    def run(self, t0: float) -> None:
        futures = []
        for i, d in enumerate(self.due):
            wait = t0 + d - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            self.late[i] = time.perf_counter() - t0 - d
            futures.append(self._pool.submit(self._client, i, t0))
        concurrent.futures.wait(futures)
        self._pool.shutdown(wait=True)


def build_server(ctx: harness.Context):
    """The tables of the seed behind the program's batching server (its
    constructor compiles and warms the one padded shape)."""
    from repro.core import mf
    from repro.launch.server import BatchingRecommender

    config, traffic = ctx.config, ctx.traffic
    user_t, item_t = make_tables(config, ctx.seed)
    state = mf.MFState(params=mf.MFParams(user_t, item_t, None), tile=None,
                       accum=None, step=jnp.zeros((), jnp.int32))
    del user_t, item_t
    ctx.log(f"tables ready at {time.perf_counter() - ctx.t0:.1f} s")
    return BatchingRecommender(
        state, traffic["k"], pruner=traffic["pruner"],
        max_batch=traffic["max_batch"], max_wait_ms=traffic["max_wait_ms"],
        similarity=config["similarity"], item_chunk=traffic["item_chunk"])


class Window(NamedTuple):
    due: np.ndarray
    users: np.ndarray
    done: np.ndarray             # answer times from the window's start (nan: none)
    late: np.ndarray             # generator lateness per request, s
    answers: list
    elapsed: float
    requests: int                # change of the server's counters
    device_calls: int


def measure(ctx: harness.Context, server, seconds: float,
            prof: harness.Profiler) -> Window:
    """Offer the seed's schedule for ``seconds`` and wait for every answer
    (at most ``WAIT_AFTER_CLOSE_S`` past the close)."""
    traffic = ctx.traffic
    due, users = schedule(ctx.seed, traffic["rate_per_s"], seconds,
                          ctx.config["num_users"])
    loop = OpenLoop(server, due, users, traffic["client_threads"],
                    timeout=seconds + WAIT_AFTER_CLOSE_S)
    before = server.stats
    prof.start()
    t0 = time.perf_counter()
    loop.run(t0)
    elapsed = time.perf_counter() - t0
    prof.stop()
    after = server.stats
    return Window(due, users, loop.done, loop.late, loop.answers, elapsed,
                  after["requests_served"] - before["requests_served"],
                  after["device_calls"] - before["device_calls"])


def sample(ctx: harness.Context, win: Window):
    """(request indices, their answers) of the checked sample: up to
    ``check_requests`` answered requests drawn from the seed."""
    rng = np.random.default_rng([ctx.seed, 1])
    ok = np.flatnonzero(np.isfinite(win.done))
    pick = rng.choice(ok, size=min(ctx.traffic["check_requests"], ok.size),
                      replace=False)
    served = (np.stack([win.answers[i] for i in pick]) if pick.size
              else np.zeros((0, ctx.traffic["k"]), np.int64))
    return pick, served


def run(ctx: harness.Context) -> harness.Outcome:
    config, traffic = ctx.config, ctx.traffic
    seconds = ctx.window_seconds
    server = build_server(ctx)
    harness.settle_host()
    setup_s = time.perf_counter() - ctx.t0
    prof = harness.Profiler(ctx.trace)
    win = measure(ctx, server, seconds, prof)
    device = harness.device_record(ctx.devices)
    server.stop()
    del server

    n = len(win.due)
    lat = win.done - win.due
    answered = np.isfinite(lat)
    failed = int(n - answered.sum())
    lat_ms = np.where(answered, lat, seconds + WAIT_AFTER_CLOSE_S) * 1e3
    late_ms = win.late * 1e3
    print(f"[serve] generator lateness ms: p50 {float(np.median(late_ms))!r}"
          f" p99 {float(np.percentile(late_ms, 99))!r}"
          f" max {float(np.max(late_ms))!r}; {n} requests, {failed} failed,"
          f" window {win.elapsed!r} s", flush=True)

    pick, served = sample(ctx, win)
    t_ref = time.perf_counter()
    rows, iq = reference_rows(config, ctx.seed, win.users[pick])
    gap = (reference.topk_gap(rows, iq, served, traffic["k"]) if pick.size
           else float("inf"))
    ctx.log(f"reference took {time.perf_counter() - t_ref:.1f} s")
    checks = {"topk_gap": (gap, float(traffic["limits"]["topk_gap"]))}
    counters = {"requests": win.requests, "device_calls": win.device_calls,
                "window_s": win.elapsed, "k": traffic["k"],
                "generator_late_p99_ms": float(np.percentile(late_ms, 99))}
    return harness.Outcome(
        correct=harness.within(checks) and failed == 0,
        attempted=n, failed=failed,
        end_to_end={"setup_s": setup_s,
                    "serve_p50_ms": float(np.percentile(lat_ms, 50)),
                    "serve_p99_ms": float(np.percentile(lat_ms, 99))},
        checks=checks, device=device, counters=counters,
        summary=prof.summary)
