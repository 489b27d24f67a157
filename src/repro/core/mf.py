"""Matrix-factorization CF model and the HEAT training step (paper §4.1).

One training step, as in Fig. 3:
  (1) gather user + positive embeddings (sparse lookups),
  (2) sample n negatives — uniform (baseline) or from the resident tile (§4.2),
  (3) optional behavior aggregation (§4.5),
  (4) fused similarity + CCL with residual reuse (§4.3, §4.4),
  (5) analytic gradients from the cached sums,
  (6) sparse row updates: only touched rows are written (§3.1 fix), with
      duplicate indices pre-reduced by scatter-add semantics (conflict-free),
  (7) aggregator grads accumulate locally, flushing every m steps (§4.5).

All steps are jittable; sampler/accumulator state is threaded functionally.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.analysis import tracing
from repro.core import aggregation as agg
from repro.core import samplers
from repro.core.engine import SampleContext, StepEngine, resolve_engine
from repro.distributed import sharding as shd
from repro.optim import quantization as qz


@dataclasses.dataclass(frozen=True)
class MFConfig:
    """Model + execution config for the HEAT MF-CF trainer (one frozen
    dataclass so it is hashable / jit-static).  ``table_format`` picks the
    embedding storage layout: ``fp32`` (plain arrays) or ``int8``
    (:class:`repro.optim.quantization.QuantizedTable` — per-row absmax
    scales, stochastic-rounded updates, error-feedback residual)."""

    num_users: int
    num_items: int
    emb_dim: int = 128
    num_negatives: int = 64
    mu: float = 1.0
    theta: float = 0.0
    similarity: str = "cosine"
    lr: float = 0.05
    # Execution backend (core/engine.py). ``backend`` picks the loss
    # implementation, ``update_impl`` the row-update path, ``sampler`` the
    # registered NegativeSampler strategy ("auto" = tile when one exists).
    backend: str = "fused"
    update_impl: str = "scatter_add"
    sampler: str = "auto"
    # Behavior aggregation (SimpleX). history_len 0 disables it (MF-CCL).
    history_len: int = 0
    aggregation_kind: str = "avg"
    gate: float = 0.5
    flush_every: int = 32          # paper's m (mini_batch_size in Listing 1)
    # Random tiling. tile_size 0 disables it (original random sampler).
    tile_size: int = 0
    refresh_interval: int = 1024
    init: str = "normal"           # "normal" | "xavier"
    init_std: float = 0.1
    dtype: str = "float32"
    # Embedding storage layout: "fp32" (plain arrays) or "int8" (quantized
    # tables — optim/quantization.py).  Orthogonal to backend/update_impl:
    # the int8 row updates replace the engine's row-update impl, everything
    # else (loss, sampler, tile) is layout-polymorphic.
    table_format: str = "fp32"


class MFParams(NamedTuple):
    """The trainable parameters: user/item tables (plain ``(R, K)`` arrays
    under ``table_format='fp32'``, :class:`~repro.optim.quantization.
    QuantizedTable` pytrees under ``'int8'``) + the optional aggregator."""

    user_table: qz.Table                           # (U, K)
    item_table: qz.Table                           # (I, K)
    aggregator: Optional[agg.AggregatorParams]     # None when history_len == 0


class MFState(NamedTuple):
    """Full training carry (donated through scan windows): params, the §4.2
    resident tile, the deferred-aggregator accumulator, and the step."""

    params: MFParams
    tile: Optional[samplers.TileState]
    accum: Optional[agg.AccumulatorState]
    step: jax.Array


def init_mf(rng: jax.Array, cfg: MFConfig) -> MFState:
    """Initialize an :class:`MFState` from the config (quantizing the fresh
    tables when ``cfg.table_format == 'int8'``)."""
    if cfg.table_format not in qz.TABLE_FORMATS:
        raise ValueError(f"unknown table_format {cfg.table_format!r}; "
                         f"available: {list(qz.TABLE_FORMATS)}")
    ku, ki, ka, kt = jax.random.split(rng, 4)
    dtype = jnp.dtype(cfg.dtype)
    if cfg.init == "xavier":
        su = jnp.sqrt(2.0 / (cfg.num_users + cfg.emb_dim))
        si = jnp.sqrt(2.0 / (cfg.num_items + cfg.emb_dim))
    else:
        su = si = cfg.init_std
    user_t = jax.random.normal(ku, (cfg.num_users, cfg.emb_dim), dtype) * su
    item_t = jax.random.normal(ki, (cfg.num_items, cfg.emb_dim), dtype) * si
    if cfg.table_format == "int8":
        user_t, item_t = qz.quantize_table(user_t), qz.quantize_table(item_t)
    params = MFParams(
        user_table=user_t,
        item_table=item_t,
        aggregator=(agg.init_aggregator(ka, cfg.emb_dim, cfg.aggregation_kind, dtype)
                    if cfg.history_len > 0 else None),
    )
    tile = (samplers.tile_init(kt, params.item_table, cfg.tile_size)
            if cfg.tile_size > 0 else None)
    accum = (agg.accumulator_init(params.aggregator)
             if params.aggregator is not None else None)
    return MFState(params=params, tile=tile, accum=accum,
                   step=jnp.zeros((), jnp.int32))


class Batch(NamedTuple):
    """One training mini-batch of implicit-feedback interactions."""

    user_ids: jax.Array                 # (B,)
    pos_ids: jax.Array                  # (B,)
    hist_ids: Optional[jax.Array] = None   # (B, H)
    hist_mask: Optional[jax.Array] = None  # (B, H)


def _forward_loss(user_e, pos_e, neg_e, hist_e, hist_mask, aggregator, cfg: MFConfig,
                  engine: StepEngine):
    """Loss as a function of *gathered* embeddings (the HEAT parallelization:
    gradients are computed w.r.t. the touched rows only, never the tables)."""
    if aggregator is not None:
        user_e = agg.aggregate(aggregator, user_e, hist_e, hist_mask,
                               gate=cfg.gate, kind=cfg.aggregation_kind)
    return engine.loss_fn(user_e, pos_e, neg_e, mu=cfg.mu, theta=cfg.theta,
                          similarity=cfg.similarity)


def heat_train_step(state: MFState, batch: Batch, rng: jax.Array, cfg: MFConfig,
                    *, engine: Optional[StepEngine] = None,
                    item_weights: Optional[jax.Array] = None):
    """One HEAT iteration.  Returns (new_state, loss).

    ``engine`` (core/engine.py) selects the loss implementation, the
    row-update implementation, and the NegativeSampler strategy; ``None``
    resolves it from ``cfg.backend`` / ``cfg.update_impl`` / ``cfg.sampler``.
    The engine is static (resolved at trace time), so the step stays jit/pjit
    compatible.  ``item_weights`` (optional, (I,)) feeds the ``popularity``
    sampler an empirical interaction distribution.
    """
    if engine is None:
        engine = resolve_engine(cfg)
    params, tile = state.params, state.tile
    with jax.named_scope(tracing.HEAT_SAMPLE):
        r_neg, r_tile = jax.random.split(rng)
    # Int8 layout: gathered rows are dequantized (inside the Pallas kernel on
    # the pallas backend, as a fused gather-multiply otherwise) and the row
    # updates requantize with stochastic rounding.  The rounding keys derive
    # from the step rng by fold_in with fixed salts — NOT by widening the
    # split above, which would perturb every existing fp32 trajectory.
    quantized = isinstance(params.user_table, qz.QuantizedTable)
    in_kernel = quantized and engine.backend == "pallas"

    with jax.named_scope(tracing.HEAT_GATHER):
        user_e = qz.gather_rows(params.user_table, batch.user_ids,
                                use_kernel=in_kernel)
        pos_e = qz.gather_rows(params.item_table, batch.pos_ids,
                               use_kernel=in_kernel)
    n_shape = (batch.user_ids.shape[0], cfg.num_negatives)

    # Negative draw through the engine's sampler protocol: the context hands
    # the strategy everything it may need (live table, resident tile, batch
    # positives, popularity weights).  The tile is read back from the
    # returned state (the protocol's slot for stateful strategies; shipped
    # samplers leave it untouched) — write-through coherence and the refresh
    # schedule stay below, after the gradient step.
    with jax.named_scope(tracing.HEAT_SAMPLE):
        drawn = engine.sampler.sample(
            SampleContext(table=params.item_table, tile=tile,
                          pos_ids=batch.pos_ids, weights=item_weights),
            r_neg, n_shape)
    neg_ids, neg_e, neg_local = drawn.ids, drawn.embs, drawn.local_idx
    tile = drawn.state.tile

    hist_e = hist_mask = None
    if params.aggregator is not None:
        with jax.named_scope(tracing.HEAT_GATHER):
            hist_e = qz.gather_rows(params.item_table, batch.hist_ids,
                                    use_kernel=in_kernel)
            hist_mask = batch.hist_mask.astype(user_e.dtype)

    def loss_fn(u, p, n, h, a):
        return _forward_loss(u, p, n, h, hist_mask, a, cfg, engine)

    argnums = (0, 1, 2) + ((3, 4) if params.aggregator is not None else ())
    with jax.named_scope(tracing.HEAT_CCL):     # the backward pass inherits it
        loss, grads = jax.value_and_grad(loss_fn, argnums=argnums)(
            user_e, pos_e, neg_e, hist_e, params.aggregator)
    g_user, g_pos, g_neg = grads[0], grads[1], grads[2]

    # Sharded execution (mf_distributed): forward/backward above is data-
    # parallel over batch rows; everything below is scatter/segment updates
    # whose operands (tables, tile, accumulator) are row-sharded or
    # replicated.  Exchange the touched-row gradients and their ids ONCE here
    # (one all-gather each under a mesh, a no-op without one): each shard
    # then applies the full update list to its own rows as a local,
    # update-order-preserving scatter — no partial-update replicas, and the
    # sharded carry tracks the single-device step to rounding.  The
    # step-shared/tile-sourced negative layouts are exactly the cheap case:
    # slot-reduction below shrinks their exchange from (B, n, K) to (N1, K).
    with jax.named_scope(tracing.HEAT_ROW_UPDATE):
        ids_user, ids_pos = map(shd.replicated,
                                (batch.user_ids, batch.pos_ids))
        g_user, g_pos, g_neg = map(shd.replicated, (g_user, g_pos, g_neg))
        neg_ids = shd.replicated(neg_ids)
        neg_local = None if neg_local is None else shd.replicated(neg_local)
        ids_hist = g_hist = None
        if params.aggregator is not None:
            ids_hist = shd.replicated(batch.hist_ids)
            g_hist = shd.replicated(grads[3])

        # §3.1/§4.3: only touched rows are written.  All of the step's item
        # gradient groups go to row_update_many in ONE call: one XLA scatter
        # for scatter_add, one cross-group pre-reduce + single gather-FMA
        # kernel launch for pallas, one dense full-table write for the torch
        # baseline of Table 1.  Scatter-add semantics everywhere, so ids
        # duplicated within or across groups accumulate and concurrent-row
        # updates cannot conflict. Tile-sourced negatives whose sample count
        # exceeds the tile are slot-reduced at the sampler boundary first: the
        # table then scatters N1 unique rows instead of B*n duplicate-heavy
        # ones, and the tile write-through becomes a dense add (the old
        # per-group double scatter was what made large tiles slower than
        # uniform sampling).  When the tile is *larger* than the sample (big
        # N1, small batch) the reduction would inflate the table write from B*n
        # to N1 rows, so the per-sample scatter path stays (shapes are static —
        # the branch resolves at trace time).
        if quantized:
            new_user = qz.apply_updates(params.user_table, ids_user, g_user,
                                        cfg.lr, jax.random.fold_in(rng, 1))
        else:
            new_user = engine.row_update(params.user_table, ids_user, g_user,
                                         cfg.lr)
        neg_reduced = None
        item_groups = [(ids_pos, g_pos)]
        if neg_local is not None and tile.tile_ids.shape[0] <= neg_local.size:
            neg_reduced = samplers.reduce_local_grads(neg_local, g_neg,
                                                      tile.tile_ids.shape[0])
            item_groups.append((tile.tile_ids, neg_reduced))
        else:
            item_groups.append((neg_ids, g_neg))
        if params.aggregator is not None:
            item_groups.append((ids_hist, g_hist))
        if quantized:
            new_item = qz.apply_updates_many(params.item_table, item_groups,
                                             cfg.lr,
                                             jax.random.fold_in(rng, 2))
        else:
            new_item = engine.row_update_many(params.item_table, item_groups,
                                              cfg.lr)

    # Tile coherence: write the same updates through to the replicated copy
    # (slot-reduced negatives as a dense add, small tile-sourced samples by
    # local-index scatter; everything addressed by global id — positives,
    # history, uniform-sourced negatives — concatenated into ONE
    # sorted-intersection pass), then refresh on schedule (§4.2).
    if tile is not None:
        with jax.named_scope(tracing.HEAT_TILE):
            global_groups = [(ids_pos, g_pos)]
            if neg_reduced is not None:
                tile = samplers.tile_apply_reduced(tile, neg_reduced, cfg.lr)
            elif neg_local is not None:
                tile = samplers.tile_apply_grads(tile, neg_local, g_neg,
                                                 cfg.lr)
            else:
                global_groups.append((neg_ids, g_neg))
            if params.aggregator is not None:
                global_groups.append((ids_hist, g_hist))
            tile = samplers.tile_apply_global_grads_many(tile, global_groups,
                                                         cfg.lr)
            tile = samplers.tile_refresh(tile, r_tile, new_item,
                                         cfg.refresh_interval)

    # Aggregator: local accumulation, deferred flush (§4.5 / Listing 1).
    aggregator, accum = params.aggregator, state.accum
    if aggregator is not None:
        with jax.named_scope(tracing.HEAT_ROW_UPDATE):
            accum = agg.accumulate(accum, grads[4])
            aggregator, accum = agg.maybe_flush(accum, aggregator, cfg.lr,
                                                cfg.flush_every)

    new_state = MFState(
        params=MFParams(new_user, new_item, aggregator),
        tile=tile, accum=accum, step=state.step + 1)
    return new_state, loss


def make_scan_body(cfg: MFConfig, batch_fn, seed: int, *,
                   engine: Optional[StepEngine] = None,
                   item_weights: Optional[jax.Array] = None):
    """``body(state, step) -> (state, loss)`` — the in-scan form of
    :func:`heat_train_step` for the ``EpochExecutor``'s dispatch windows.

    ``batch_fn(step)`` builds the batch from a *traced* step index (e.g.
    ``pipeline.cf_batch_device`` over a device-resident dataset), and the
    per-step rng is ``fold_in(PRNGKey(seed), step)`` — exactly the derivation
    the per-step driver loop uses, so a scanned window reproduces the
    per-step trajectory bit-for-bit and a restart is pure in (seed, step).
    Every engine combination is scan-carry-compatible: ``MFState`` threads
    the tile and aggregator-accumulator states functionally, the engine (and
    ``item_weights``, e.g. ``DeviceCFDataset.item_weights`` feeding the
    ``popularity`` sampler) is a static closure, and branch structure
    resolves at trace time.
    """
    if engine is None:
        engine = resolve_engine(cfg)
    base = jax.random.PRNGKey(seed)

    def body(state: MFState, step: jax.Array):
        with jax.named_scope(tracing.HEAT_BATCH):
            batch = batch_fn(step)
            rng = jax.random.fold_in(base, step)
        return heat_train_step(state, batch, rng, cfg, engine=engine,
                               item_weights=item_weights)

    return body


def _score_item_block(u: jax.Array, block: jax.Array,
                      similarity: str) -> jax.Array:
    """(B, K) users x (C, K) item rows -> (B, C) scores."""
    s = u @ block.T
    if similarity == "cosine":
        un = jnp.linalg.norm(u, axis=-1, keepdims=True).clip(1e-12)
        bn = jnp.linalg.norm(block, axis=-1).clip(1e-12)
        s = s / un / bn[None, :]
    return s


def scores_all_items(params: MFParams, user_ids: jax.Array,
                     similarity: str = "cosine", *,
                     item_chunk: Optional[int] = None) -> jax.Array:
    """(B, I) scores for evaluation (Recall@K / NDCG@K).

    ``item_chunk`` computes the matrix block-by-block (bounded matmul
    temporaries); the result is still (B, I) — use :func:`topk_all_items`
    when only a top-k is needed and (B, I) must never exist at once.
    """
    u = qz.gather_rows(params.user_table, user_ids)
    t = params.item_table
    n = qz.num_rows(t)
    if not item_chunk or item_chunk >= n:
        return _score_item_block(u, qz.dequantize_table(t), similarity)
    blocks = [_score_item_block(u, qz.slice_rows(t, s, s + item_chunk),
                                similarity)
              for s in range(0, n, item_chunk)]
    return jnp.concatenate(blocks, axis=1)


def topk_all_items(params: MFParams, user_ids: jax.Array, k: int, *,
                   similarity: str = "cosine",
                   item_chunk: Optional[int] = None,
                   exclude_mask: Optional[jax.Array] = None) -> jax.Array:
    """Top-k item ids per user over the full catalog, chunked.

    With ``item_chunk`` below the catalog size, one Pallas kernel
    (``kernels/topk_scan.py``) streams the item table and keeps a running
    (B, k) top-k in on-chip memory, merging a (B, item_chunk) score block
    (``item_chunk`` rounded down to whole 128-lane tiles, 128 to 512) only
    when one of its scores beats its row's k-th.  So the full (B, I) score matrix is **never materialized**
    and the compiled program is O(1) in the chunk count — the serving /
    full-catalog-evaluation path for paper-scale item counts (9.4M items at
    Table 3 scale would be a 38 GB score matrix for a 1k-user batch).
    ``exclude_mask`` (B, I) bool masks training positives (read per block).
    Ties go to the lowest item id.  ``k > num_items`` is clamped: the
    result is (B, min(k, I)) — every item ranked, no phantom ids.
    """
    t = params.item_table
    num_items = qz.num_rows(t)
    k = min(int(k), num_items)
    c = item_chunk or num_items
    with jax.named_scope(tracing.TOPK_PREPARE):
        u = qz.gather_rows(params.user_table, user_ids)
    with jax.named_scope(tracing.TOPK_SCAN):
        if c >= num_items:
            sc = _score_item_block(u, qz.dequantize_table(t), similarity)
            if exclude_mask is not None:
                sc = jnp.where(exclude_mask, -jnp.inf, sc)
            return jax.lax.top_k(sc, k)[1]
        from repro.kernels import ops
        q, scale = ((t.q, t.scale) if isinstance(t, qz.QuantizedTable)
                    else (t, None))
        return ops.topk_scan(u, q, scale, k, similarity=similarity,
                             item_chunk=c, exclude_mask=exclude_mask)[0]
