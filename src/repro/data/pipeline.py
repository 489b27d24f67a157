"""Synthetic data pipelines: implicit-feedback CF and LM token streams.

Determinism & restart: every batch is a pure function of (seed, step), so a
job restored from a step-N checkpoint resumes on exactly the batch it would
have seen — no iterator state to persist (DESIGN.md §5 fault tolerance).
The (seed, step) mix is an **explicit stable derivation** — counter-based
threefry ``fold_in(PRNGKey(seed), step)`` — never CPython ``hash`` (tuple
hashes are an implementation detail and string hashes are salted per
process, so a restart could silently resume on different data).

Steady-state training does not run host numpy at all: a
:class:`DeviceCFDataset` keeps ``train_pos`` (and popularity weights) as
device arrays and :func:`cf_batch_device` is jit/scan-traceable, so the
``EpochExecutor`` (train/trainer.py) samples batches *inside* the compiled
dispatch window.  The host-side :func:`cf_batch` evaluates the same
derivation eagerly — host and device batches are bit-identical
(tests/test_pipeline.py), which is what lets the per-step loop and the
scanned executor produce the same trajectory.

CF generator: power-law item popularity + per-user preference clusters so
that embeddings are learnable (recall rises above the random baseline within
a few hundred steps — exercised by benchmarks/bench_accuracy.py).

Streaming (src/repro/stream/): the device dataset doubles as *incremental*
state.  :func:`stream_ring_dataset` lays each user's positives out as a
fixed-capacity ring, :meth:`DeviceCFDataset.apply_events` folds a micro-batch
of live (user, item) events into it **on device** (append/evict rows, update
popularity counts — no table re-upload, one trace per event-batch shape), and
:func:`stream_batch_device` samples training batches recency-weighted over
the ring.  ``DeviceCFDataset`` is a registered pytree so it can ride the
``EpochExecutor``'s scanned carry and the checkpoint machinery.
"""
from __future__ import annotations

import dataclasses
import weakref
import zlib
from typing import Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.sanitize import TraceCounter
from repro.core.mf import Batch


@dataclasses.dataclass(frozen=True)
class CFDataset:
    """Dense interaction matrix view of a synthetic implicit-feedback set."""

    num_users: int
    num_items: int
    train_pos: np.ndarray       # (num_users, max_train) int32, -1 padded
    test_pos: np.ndarray        # (num_users, max_test) int32, -1 padded

    def train_mask(self) -> np.ndarray:
        m = np.zeros((self.num_users, self.num_items), bool)
        u = np.repeat(np.arange(self.num_users), self.train_pos.shape[1])
        i = self.train_pos.reshape(-1)
        valid = i >= 0
        m[u[valid], i[valid]] = True
        return m

    def test_mask(self) -> np.ndarray:
        m = np.zeros((self.num_users, self.num_items), bool)
        u = np.repeat(np.arange(self.num_users), self.test_pos.shape[1])
        i = self.test_pos.reshape(-1)
        valid = i >= 0
        m[u[valid], i[valid]] = True
        return m


def synth_cf_dataset(num_users: int, num_items: int, *, seed: int = 0,
                     interactions_per_user: int = 20, num_clusters: int = 16,
                     test_frac: float = 0.2) -> CFDataset:
    """Clustered power-law interactions: user u prefers items from its
    cluster's popularity-ranked pool, making CF signal recoverable.

    Each user takes ``min(interactions_per_user, pool size)`` distinct items
    of its pool, drawn one after another with probability proportional to
    1/rank among the items not yet drawn.  All users of a cluster draw
    together (:func:`_distinct_power_law`), so 400k users take seconds."""
    rng = np.random.default_rng(seed)
    user_cluster = rng.integers(0, num_clusters, num_users)
    item_cluster = rng.integers(0, num_clusters, num_items)
    pools = [np.where(item_cluster == c)[0] for c in range(num_clusters)]
    pools = [p if len(p) else np.arange(num_items) for p in pools]

    n_test = max(int(interactions_per_user * test_frac), 1)
    n_train = interactions_per_user - n_test
    train = np.full((num_users, n_train), -1, np.int32)
    test = np.full((num_users, n_test), -1, np.int32)
    for c, pool in enumerate(pools):
        users = np.flatnonzero(user_cluster == c)
        k = min(interactions_per_user, len(pool))
        items = pool[_distinct_power_law(rng, len(pool), users.size, k)]
        n_tr = max(k - n_test, 0)
        train[users, :n_tr] = items[:, :n_tr]
        test[users, :min(n_test, k)] = items[:, n_tr:k]
    return CFDataset(num_users, num_items, train, test)


def _distinct_power_law(rng: np.random.Generator, pool_size: int, rows: int,
                        k: int) -> np.ndarray:
    """(rows, k) indices into a pool, distinct within each row, drawn one
    after another with P(rank r) proportional to 1/(r+1) among the ranks not
    yet drawn.  Each row draws with replacement and rejects repeats (which
    is that successive sampling exactly); rows short of k distinct ranks
    draw again."""
    cdf = np.cumsum(1.0 / np.arange(1, pool_size + 1))
    cdf /= cdf[-1]
    out = np.empty((rows, k), np.int64)
    todo = np.arange(rows)
    draws = np.empty((rows, 0), np.int64)
    while todo.size:
        fresh = np.searchsorted(cdf, rng.random((todo.size, 2 * k)),
                                side="right")
        draws = np.concatenate([draws, np.minimum(fresh, pool_size - 1)],
                               axis=1)
        # first occurrence of each value in its row, in draw order
        order = np.argsort(draws, axis=1, kind="stable")
        ranked = np.take_along_axis(draws, order, axis=1)
        new_sorted = np.ones(ranked.shape, bool)
        new_sorted[:, 1:] = ranked[:, 1:] != ranked[:, :-1]
        first = np.empty_like(new_sorted)
        np.put_along_axis(first, order, new_sorted, axis=1)
        done = first.sum(axis=1) >= k
        keep = np.argsort(~first[done], axis=1, kind="stable")[:, :k]
        out[todo[done]] = np.take_along_axis(draws[done], keep, axis=1)
        todo, draws = todo[~done], draws[~done]
    return out


@dataclasses.dataclass(frozen=True)
class DeviceCFDataset:
    """Device-resident view of a :class:`CFDataset` (the executor's input).

    ``train_pos`` lives on the accelerator so in-scan batch sampling never
    copies from the host; ``item_weights`` holds the empirical interaction
    counts (the ``popularity`` sampler's natural weights) as a device array
    for the same reason.  Static ints stay Python ints — they size the
    compiled program, they are not traced.

    Streaming views (:func:`stream_ring_dataset`) additionally carry ring
    state — ``row_count`` (valid rows per user, saturating at the column
    capacity) and ``write_pos`` (next slot to write, mod capacity) — so
    :meth:`apply_events` can append/evict in place.  Offline views leave
    them ``None``.  The class is a registered pytree (array fields are
    leaves, the sizing ints are static metadata), so a streaming view
    threads through scanned carries and checkpoints like any state."""

    num_users: int
    num_items: int
    train_pos: jax.Array            # (num_users, capacity) int32, -1 padded
    item_weights: jax.Array         # (num_items,) float32 interaction counts
    row_count: Optional[jax.Array] = None   # (num_users,) int32 valid rows
    write_pos: Optional[jax.Array] = None   # (num_users,) int32 ring cursor

    def apply_events(self, user_ids, item_ids):
        """Fold one micro-batch of (user, item) events into the view.

        ``user_ids`` / ``item_ids``: equal-length int32 arrays; ``user_id
        < 0`` marks padding (callers pad event batches to a fixed size so
        every micro-batch hits the same compiled program — one trace per
        distinct length, counted by ``APPLY_EVENTS_TRACES``).  Each event
        appends its item to the user's ring (overwriting the oldest entry
        once ``row_count`` saturates at capacity) and bumps the item's
        popularity count.

        Returns ``(new_view, new_user_mask, new_item_mask)`` where the masks
        flag users/items seen for the first time (callers initialize fresh
        embedding rows from them).  The input view's buffers are **donated**
        — use the returned view only (which is why offline memoized views,
        shared by reference, refuse this method)."""
        if self.row_count is None or self.write_pos is None:
            raise ValueError(
                "apply_events needs ring state (row_count/write_pos); build "
                "the view with stream_ring_dataset(...) — offline "
                "device_cf_dataset views are shared/memoized and must stay "
                "immutable")
        users = jax.device_put(np.asarray(user_ids, np.int32))
        items = jax.device_put(np.asarray(item_ids, np.int32))
        if users.shape != items.shape or users.ndim != 1:
            raise ValueError(f"event arrays must be equal-length 1-D, got "
                             f"{users.shape} vs {items.shape}")
        tp, iw, rc, wp, new_u, new_i = _apply_events_jit(
            self.train_pos, self.item_weights, self.row_count,
            self.write_pos, users, items)
        view = dataclasses.replace(self, train_pos=tp, item_weights=iw,
                                   row_count=rc, write_pos=wp)
        return view, new_u, new_i


jax.tree_util.register_dataclass(
    DeviceCFDataset,
    data_fields=["train_pos", "item_weights", "row_count", "write_pos"],
    meta_fields=["num_users", "num_items"])


#: one trace per distinct event-batch length — re-tracing per micro-batch
#: would mean the ingest path recompiles in steady state (tests arm a budget
#: via ``APPLY_EVENTS_TRACES.check(budget=...)``).
APPLY_EVENTS_TRACES = TraceCounter("device_cf_dataset.apply_events")


def _apply_events_impl(train_pos, item_weights, row_count, write_pos,
                       users, items):
    """Sequential ring fold over one padded event batch.

    The per-event ``fori_loop`` preserves arrival order, so duplicate users
    within one micro-batch append in sequence (a vectorized scatter would
    collapse them to one slot).  Event count per micro-batch is small
    (hundreds), so the sequential loop is not the bottleneck — the tables
    it indexes stay resident and donated."""
    capacity = train_pos.shape[1]
    valid = users >= 0
    seen_user = row_count > 0
    seen_item = item_weights > 0
    # popularity counts: one masked scatter-add (padding rows add 0 to row 0)
    item_weights = item_weights.at[jnp.where(valid, items, 0)].add(
        valid.astype(item_weights.dtype))

    def body(i, carry):
        tp, rc, wp = carry
        ok = users[i] >= 0
        u = jnp.where(ok, users[i], 0)
        slot = wp[u]
        tp = tp.at[u, slot].set(jnp.where(ok, items[i], tp[u, slot]))
        wp = wp.at[u].set(jnp.where(ok, (slot + 1) % capacity, slot))
        rc = rc.at[u].set(jnp.where(ok, jnp.minimum(rc[u] + 1, capacity),
                                    rc[u]))
        return tp, rc, wp

    train_pos, row_count, write_pos = jax.lax.fori_loop(
        0, users.shape[0], body, (train_pos, row_count, write_pos))
    new_users = (row_count > 0) & ~seen_user
    new_items = (item_weights > 0) & ~seen_item
    return train_pos, item_weights, row_count, write_pos, new_users, new_items


_apply_events_jit = jax.jit(APPLY_EVENTS_TRACES.wrap(_apply_events_impl),
                            donate_argnums=(0, 1, 2, 3))


_DEVICE_VIEWS: dict[int, DeviceCFDataset] = {}


def device_cf_dataset(ds: CFDataset, *,
                      allow_empty_users: Optional[bool] = None
                      ) -> DeviceCFDataset:
    """Upload ``train_pos`` + popularity weights once, ahead of the epoch.

    Memoized per ``CFDataset`` instance (dropped when the dataset is
    garbage-collected), so repeated callers — the executor, the per-step
    ``cf_batch``, popularity-weight consumers — share one device copy
    instead of re-uploading the table.  Datasets are treated as immutable
    (streaming needs a private, mutable-by-replacement view — that is
    :func:`stream_ring_dataset`).

    Zero-interaction users have an *empty sample range*: a batch row drawn
    for them has no positive to gather.  ``allow_empty_users`` controls the
    contract:

    * ``None`` (default): empty users are tolerated — their rows fall back
      to a **uniform item draw** in the batch derivation (documented in
      :func:`_cf_batch_from`) — but an *all*-empty dataset (the cold-start
      stream case) raises, because every batch row would be fallback noise;
      cold starts belong to :func:`stream_ring_dataset`.
    * ``False``: any empty user raises (strict offline mode).
    * ``True``: anything goes (the caller owns sampling).
    """
    empty = ~(ds.train_pos >= 0).any(axis=1)
    if allow_empty_users is not True:
        if empty.all() and ds.num_users > 0:
            raise ValueError(
                "every user has zero train interactions — an offline device "
                "view would sample pure fallback noise.  For cold-start "
                "streaming build the view with stream_ring_dataset(...) and "
                "feed it events via apply_events; pass "
                "allow_empty_users=True to override")
        if allow_empty_users is False and empty.any():
            raise ValueError(
                f"{int(empty.sum())} user(s) have zero train interactions "
                "(empty sample ranges); their batch rows fall back to a "
                "uniform item draw — pass allow_empty_users=None to accept "
                "the fallback or clean the dataset")
    view = _DEVICE_VIEWS.get(id(ds))
    if view is None:
        valid = ds.train_pos[ds.train_pos >= 0]
        counts = np.bincount(valid.ravel(), minlength=ds.num_items)
        view = DeviceCFDataset(ds.num_users, ds.num_items,
                               jnp.asarray(ds.train_pos, jnp.int32),
                               jnp.asarray(counts, jnp.float32))
        _DEVICE_VIEWS[id(ds)] = view
        weakref.finalize(ds, _DEVICE_VIEWS.pop, id(ds), None)
    return view


def stream_ring_dataset(num_users: int, num_items: int,
                        capacity: int = 32, *,
                        base: Optional[CFDataset] = None) -> DeviceCFDataset:
    """A *streaming* device view: per-user positives in a fixed-capacity ring.

    ``base=None`` starts cold — empty rings, zero popularity (legal here,
    unlike :func:`device_cf_dataset`, because the streaming batch sampler
    restricts its user draw to users with ``row_count > 0`` and the service
    loop does not train before the first event).  With ``base``, the ring is
    warm-started from the newest ``capacity`` stored positives per user and
    the popularity counts recomputed from exactly what the ring holds.

    The returned view is **private** (never memoized): ``apply_events``
    donates its buffers, which must not alias a view other callers share.
    """
    if capacity < 1:
        raise ValueError(f"ring capacity must be >= 1, got {capacity}")
    train = np.full((num_users, capacity), -1, np.int32)
    if base is not None:
        if (base.num_users, base.num_items) != (num_users, num_items):
            raise ValueError(
                f"base dataset is {base.num_users}x{base.num_items}, "
                f"asked for {num_users}x{num_items}")
        for u in range(num_users):
            row = base.train_pos[u]
            row = row[row >= 0][-capacity:]
            train[u, :row.size] = row
    counts = np.bincount(train[train >= 0].ravel(), minlength=num_items)
    row_count = (train >= 0).sum(axis=1).astype(np.int32)
    return DeviceCFDataset(
        num_users, num_items,
        jnp.asarray(train, jnp.int32),
        jnp.asarray(counts, jnp.float32),
        row_count=jnp.asarray(row_count),
        write_pos=jnp.asarray(row_count % capacity))


def _cf_batch_from(train_pos: jax.Array, num_users: int, num_items: int,
                   step, batch_size: int,
                   history_len: int, seed: int) -> Batch:
    """The one (seed, step)-pure batch derivation, shared by the host and
    device entry points.  ``step`` may be a traced int32 (in-scan use); the
    mix is threefry ``fold_in`` — explicit and stable, no CPython hash.

    Fallback chain for padded slots: a drawn -1 resamples from column 0;
    a user whose *whole row* is empty (zero interactions) falls back to a
    uniform item draw — documented behavior, guarded at view construction
    by ``device_cf_dataset(allow_empty_users=...)``.  The uniform key is
    ``fold_in(key, 7)`` (not a wider split) so users/cols draws — and with
    them every trajectory of a dataset with no empty users — are unchanged."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
    ku, kc = jax.random.split(key)
    users = jax.random.randint(ku, (batch_size,), 0, num_users, jnp.int32)
    cols = jax.random.randint(kc, (batch_size,), 0, train_pos.shape[1],
                              jnp.int32)
    pos = train_pos[users, cols]
    # replace -1 (padded) with a resample from column 0
    pos = jnp.where(pos >= 0, pos, train_pos[users, 0])
    uniform = jax.random.randint(jax.random.fold_in(key, 7), (batch_size,),
                                 0, num_items, jnp.int32)
    pos = jnp.where(pos >= 0, pos, uniform).astype(jnp.int32)
    hist_ids = hist_mask = None
    if history_len > 0:
        h = train_pos[users, :history_len]
        hist_mask = (h >= 0).astype(jnp.float32)
        hist_ids = jnp.where(h >= 0, h, 0).astype(jnp.int32)
    return Batch(user_ids=users, pos_ids=pos,
                 hist_ids=hist_ids, hist_mask=hist_mask)


def cf_batch(ds: CFDataset, step: int, batch_size: int, history_len: int = 0,
             seed: int = 0) -> Batch:
    """Pure function of (seed, step): sample users + one train positive each.

    Host-side entry point (numpy dataset in, eager evaluation) — bit-identical
    to :func:`cf_batch_device` on the same (seed, step) by construction.  The
    device view of ``train_pos`` is memoized, so per-step calls don't
    re-upload the table."""
    return _cf_batch_from(device_cf_dataset(ds).train_pos, ds.num_users,
                          ds.num_items, step, batch_size, history_len, seed)


def cf_batch_device(ds: DeviceCFDataset, seed: int, step, batch_size: int,
                    history_len: int = 0) -> Batch:
    """Jit/scan-traceable batch sampling over the device-resident dataset:
    ``step`` may be a traced scalar (the ``lax.scan`` index inside an
    ``EpochExecutor`` dispatch window), so steady-state training runs no host
    numpy and copies nothing to the device per step."""
    return _cf_batch_from(ds.train_pos, ds.num_users, ds.num_items, step,
                          batch_size, history_len, seed)


def stream_batch_device(ds: DeviceCFDataset, seed: int, step,
                        batch_size: int, *, recency: float = 0.0,
                        history_len: int = 0) -> Batch:
    """Recency-weighted batch over a streaming ring view — jit/scan-traceable
    (``step`` may be the traced scan index), pure in (seed, step, ring state).

    Users are drawn uniformly over users with at least one ingested positive
    (``row_count > 0`` — the cold-start guard the offline sampler lacks);
    each drawn user contributes its positive at ring *age* ``a`` (0 = newest)
    with ``a`` from a truncated geometric, ``P(a) ∝ exp(-recency * a)`` over
    the user's valid ages — ``recency=0`` degenerates to uniform-over-ring,
    larger values concentrate training on what just arrived (the freshness
    knob the SLO bench sweeps).  The key is decorrelated from the train
    step's ``fold_in(PRNGKey(seed), step)`` by one extra fold.

    Degenerate case (no user has any event yet): the masked user draw
    collapses to user 0 / its empty ring falls back to item 0.  The service
    loop never trains before the first ingested event, so this is never a
    trained-on batch — documented rather than guarded here to keep the
    derivation branch-free and traceable."""
    capacity = ds.train_pos.shape[1]
    if ds.row_count is None or ds.write_pos is None:
        raise ValueError("stream_batch_device needs a ring view "
                         "(stream_ring_dataset), not an offline one")
    key = jax.random.fold_in(
        jax.random.fold_in(jax.random.PRNGKey(seed), step), 1)
    ku, ka = jax.random.split(key)
    has_events = ds.row_count > 0
    logits = jnp.where(has_events, 0.0, -jnp.inf)
    users = jax.random.categorical(ku, logits, shape=(batch_size,)
                                   ).astype(jnp.int32)
    count = jnp.maximum(ds.row_count[users], 1).astype(jnp.float32)
    u01 = jax.random.uniform(ka, (batch_size,))
    if recency > 0.0:
        # inverse CDF of the truncated geometric over ages [0, count)
        q = float(np.exp(-recency))
        age = jnp.floor(jnp.log1p(-u01 * (1.0 - q ** count)) / np.log(q))
    else:
        age = jnp.floor(u01 * count)
    age = jnp.clip(age, 0, count - 1).astype(jnp.int32)
    cols = (ds.write_pos[users] - 1 - age) % capacity
    pos = ds.train_pos[users, cols]
    pos = jnp.where(pos >= 0, pos, 0).astype(jnp.int32)
    hist_ids = hist_mask = None
    if history_len > 0:
        # history = the user's most recent ``history_len`` ring entries
        h_age = jnp.arange(history_len, dtype=jnp.int32)[None, :]
        h_cols = (ds.write_pos[users, None] - 1 - h_age) % capacity
        h = ds.train_pos[users[:, None], h_cols]
        h_ok = (h_age < ds.row_count[users, None]) & (h >= 0)
        hist_mask = h_ok.astype(jnp.float32)
        hist_ids = jnp.where(h_ok, h, 0).astype(jnp.int32)
    return Batch(user_ids=users, pos_ids=pos,
                 hist_ids=hist_ids, hist_mask=hist_mask)


def shard_bounds(global_batch: int, num_shards: int) -> list[tuple[int, int]]:
    """Contiguous [start, stop) row ranges partitioning a global batch.

    Remainder rows (``global_batch % num_shards``) go one-per-shard to the
    lowest shard indices, so sizes differ by at most one and the concatenation
    of all shards is exactly the global batch — no row dropped or duplicated
    at any (batch, num_shards), which is what lets uneven batches shard.
    """
    if num_shards < 1:
        raise ValueError(f"num_shards must be >= 1, got {num_shards}")
    base, rem = divmod(global_batch, num_shards)
    bounds, start = [], 0
    for s in range(num_shards):
        stop = start + base + (1 if s < rem else 0)
        bounds.append((start, stop))
        start = stop
    return bounds


def cf_batch_shard(ds: DeviceCFDataset, seed: int, step, global_batch: int,
                   shard: int, num_shards: int,
                   history_len: int = 0) -> Batch:
    """Shard ``shard``'s rows of the *global* (seed, step) batch.

    The derivation is the same threefry draw as :func:`cf_batch` /
    :func:`cf_batch_device` — every shard evaluates the full (cheap, id-only)
    derivation and slices its contiguous row range, so concatenating the
    shards reproduces the single-device batch **bit-exactly** (asserted by a
    hypothesis test over uneven ``batch % num_shards`` remainders).  This is
    the per-host entry point for multi-host data loading; within one process
    the GSPMD path instead samples the full batch in-program and pins it to
    the data axes (``MFShardingPlan.constrain_batch``) — same values, zero
    host work.  Partitionable threefry (enabled at package import) is what
    makes the values independent of where they are computed.
    """
    start, stop = shard_bounds(global_batch, num_shards)[shard]
    full = _cf_batch_from(ds.train_pos, ds.num_users, ds.num_items, step,
                          global_batch, history_len, seed)
    return jax.tree.map(lambda x: x[start:stop], full)


def procedural_cf_batch(step: int, batch_size: int, num_users: int,
                        num_items: int, num_clusters: int = 64,
                        seed: int = 0) -> Batch:
    """Million-row-scale CF batches without materializing a dataset.

    User u belongs to cluster u % C; its positives are drawn (power-law-ish)
    from that cluster's contiguous item block — pure function of (seed, step),
    so checkpoint-restart determinism holds at any table size.
    """
    key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
    ku, ko = jax.random.split(key)
    users = jax.random.randint(ku, (batch_size,), 0, num_users, jnp.int32)
    block = max(num_items // num_clusters, 1)
    # power-law offset within the cluster block: floor(block * u^3)
    u = jax.random.uniform(ko, (batch_size,))
    offset = jnp.minimum((block * u ** 3).astype(jnp.int32), block - 1)
    pos = (users % num_clusters) * block + offset
    return Batch(user_ids=users, pos_ids=jnp.minimum(pos, num_items - 1))


def lm_batch(step: int, batch_size: int, seq_len: int, vocab: int,
             seed: int = 0, extras: Optional[dict] = None) -> dict:
    """Synthetic LM batch — pure function of (seed, step).

    Markov-ish structure (token t+1 correlated with t) so the loss has
    learnable signal for the end-to-end examples.  ``step`` may be a traced
    scalar: the LM executor samples batches inside its scanned windows too.
    """
    key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
    k1, k2 = jax.random.split(key)
    base = jax.random.randint(k1, (batch_size, seq_len), 0, vocab, jnp.int32)
    # 50% of positions copy their predecessor (compressible structure)
    copy = jax.random.bernoulli(k2, 0.5, (batch_size, seq_len))
    shifted = jnp.concatenate([base[:, :1], base[:, :-1]], axis=1)
    tokens = jnp.where(copy, shifted, base)
    batch = {"tokens": tokens}
    if extras:
        for name, (shape, dtype) in extras.items():
            # crc32, not hash(): str hashes are salted per process, so a
            # restarted job would resume on different extras.
            kk = jax.random.fold_in(k2, zlib.crc32(name.encode()) & 0x7FFFFFFF)
            batch[name] = (jax.random.normal(kk, shape, dtype) * 0.1)
    return batch
