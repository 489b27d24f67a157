"""Serving launcher: batched LM decoding loop (prefill -> decode_step*) or
MF top-k recommendation serving.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --reduced \
        --prompt-len 16 --decode-steps 8 --batch 4
    PYTHONPATH=src python -m repro.launch.serve --mf --topk 10 \
        --pruner tile --expand-tiles 4 --max-batch 32 --max-wait-ms 2
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp


def serve_mf(args) -> None:
    """MF top-k recommendation serving through the unified engine API.

    Trains briefly (``resolve_engine`` picks the execution backend), then
    stands up a :class:`repro.launch.server.BatchingRecommender`: the
    serving path is traced + compiled at startup (cold-start is paid before
    the first request, asserted via the server's trace counter), concurrent
    single-user requests are coalesced into one (B, ·) device call behind a
    ``--max-wait-ms`` deadline, and ``--pruner tile`` swaps the chunked
    exact ``mf.topk_all_items`` for the tile-pruned candidate path
    (``retrieval.topk_pruned``, expansion budget ``--expand-tiles``).  The
    served tables are the trainer's device-resident ``MFState`` — after an
    online training burst, ``refresh_from`` re-points the compiled program
    at the new tables (and re-centers the index) without a host round-trip.
    """
    import threading

    import numpy as np

    from repro.core import mf, retrieval
    from repro.core.engine import resolve_engine
    from repro.data import pipeline
    from repro.launch.server import BatchingRecommender
    from repro.train import trainer

    users, items = 1000, 2000
    ds = pipeline.synth_cf_dataset(users, items, interactions_per_user=16,
                                   num_clusters=16, seed=0)
    cfg = mf.MFConfig(num_users=users, num_items=items, emb_dim=64,
                      num_negatives=32, lr=0.1, tile_size=256,
                      refresh_interval=128,
                      backend=args.backend or "fused",
                      sampler=args.sampler or "auto")
    engine = resolve_engine(cfg)
    print(f"[serve] MF engine: {engine.name}")
    state, _ = trainer.train_mf(cfg, ds, steps=args.train_steps,
                                batch_size=128, engine=engine,
                                log=lambda *_: None)

    index = None
    if args.pruner == "tile":
        index = retrieval.build_retrieval_index(
            state.params.item_table, tile_rows=args.tile_rows)
        print(f"[serve] pruner=tile: {index.num_tiles} tiles x "
              f"{index.tile_rows} rows, expanding {args.expand_tiles}")

    train_mask = jnp.asarray(ds.train_mask())
    t0 = time.perf_counter()
    server = BatchingRecommender(
        state, args.topk, pruner=args.pruner, index=index,
        expand_tiles=args.expand_tiles, max_batch=args.max_batch,
        max_wait_ms=args.max_wait_ms, item_chunk=args.item_chunk,
        exclude_mask=train_mask)
    print(f"[serve] warmup (trace+compile) in "
          f"{1e3 * (time.perf_counter() - t0):.1f} ms; "
          f"traces={server.trace_count}")

    # Concurrent single-user clients: the queue coalesces them into (B, ·)
    # device calls behind the max-wait deadline.
    rng = np.random.default_rng(0)
    n_requests, lat_ms = 256, []
    lock = threading.Lock()

    def client(uid: int):
        t = time.perf_counter()
        server.recommend(uid)
        with lock:
            lat_ms.append(1e3 * (time.perf_counter() - t))

    t0 = time.perf_counter()
    threads = [threading.Thread(target=client,
                                args=(int(rng.integers(0, users)),))
               for _ in range(n_requests)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    lat = np.sort(lat_ms)
    stats = server.stats
    print(f"[serve] {n_requests} concurrent requests in {wall * 1e3:.1f} ms: "
          f"qps={n_requests / wall:,.0f} "
          f"p50={lat[len(lat) // 2]:.2f} ms "
          f"p99={lat[int(len(lat) * 0.99)]:.2f} ms "
          f"({stats['device_calls']} device calls, "
          f"traces={stats['traces']})")

    uid = int(rng.integers(0, users))
    recs = server.recommend(uid)
    print(f"[serve] top-{args.topk} for user {uid} ({args.pruner}): "
          f"{recs[:5]}")

    # Online refresh: warm-start the streaming service on the trained state
    # (state + a ring view over the offline dataset are *consumed* — training
    # donates their buffers) and run a couple of live ingest → train →
    # refresh_from rounds against this very server.  This is the one blessed
    # online-refresh path; see repro.stream.service.StreamingTrainer.
    from repro.stream.service import StreamingConfig, StreamingTrainer
    from repro.stream.sources import SyntheticStream

    live = SyntheticStream(users, items, seed=1, total=512,
                           user_drift=0.01, item_drift=0.01)
    streamer = StreamingTrainer(
        cfg, live,
        StreamingConfig(capacity=16, micro_batch=256, steps_per_round=25,
                        batch_size=128, seed=0),
        state=state,
        data=pipeline.stream_ring_dataset(users, items, 16, base=ds),
        engine=engine, recommender=server, log=lambda *_: None)
    del state                                   # donated to the service loop
    streamer.run(rounds=2)
    recs2 = server.recommend(uid)
    print(f"[serve] after {streamer.rounds} streaming rounds "
          f"({streamer.events} live events, {streamer.step} total steps, "
          f"no retrace: traces={server.trace_count}): {recs2[:5]}")
    server.stop()


def main():
    """CLI entry for the batching recommendation server demo."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--decode-steps", type=int, default=8)
    ap.add_argument("--mf", action="store_true",
                    help="serve MF top-k recommendations instead of LM decode")
    ap.add_argument("--topk", type=int, default=10)
    ap.add_argument("--item-chunk", type=int, default=512,
                    help="catalog chunk for the running top-k merge")
    ap.add_argument("--pruner", choices=("exact", "tile"), default="exact",
                    help="exact: chunked full-catalog top-k; tile: "
                         "tile-pruned candidates (retrieval.topk_pruned)")
    ap.add_argument("--expand-tiles", type=int, default=4,
                    help="tile pruner expansion budget (top-T tiles whose "
                         "members get exact scoring)")
    ap.add_argument("--tile-rows", type=int, default=128,
                    help="index tile size (rows per tile)")
    ap.add_argument("--max-batch", type=int, default=32,
                    help="request coalescing: max requests per device call")
    ap.add_argument("--max-wait-ms", type=float, default=2.0,
                    help="request coalescing: max wait for a fuller batch")
    ap.add_argument("--train-steps", type=int, default=300)
    ap.add_argument("--backend", default=None)
    ap.add_argument("--sampler", default=None)
    args = ap.parse_args()

    if args.mf:
        serve_mf(args)
        return

    from repro.configs import get_config
    from repro.models import lm

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    opts = lm.TrainOptions(loss="softmax", remat="none",
                           attn_chunk=min(1024, args.prompt_len))
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    max_len = args.prompt_len + args.decode_steps

    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1),
                                          (args.batch, args.prompt_len), 0,
                                          cfg.vocab)}
    if cfg.family == "audio":
        batch["frames"] = jnp.zeros((args.batch, cfg.encoder_seq, cfg.d_model))
    if cfg.family == "vlm":
        batch["patches"] = jnp.zeros((args.batch, cfg.num_patches, cfg.d_model))

    t0 = time.perf_counter()
    logits, cache = lm.prefill(params, batch, cfg, opts)
    cache = lm.pad_cache(cache, cfg, max_len)
    jax.block_until_ready(logits)
    t_prefill = time.perf_counter() - t0
    print(f"prefill: {args.batch}x{args.prompt_len} tokens in "
          f"{1e3 * t_prefill:.1f} ms")

    decode = jax.jit(lambda c, t, p: lm.decode_step(params, c, t, p, cfg, opts))
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    generated = [tok]
    t0 = time.perf_counter()
    for i in range(args.decode_steps):
        logits_t, cache = decode(cache, tok, jnp.asarray(args.prompt_len + i,
                                                         jnp.int32))
        tok = jnp.argmax(logits_t[:, 0], -1)[:, None].astype(jnp.int32)
        generated.append(tok)
    jax.block_until_ready(tok)
    dt = (time.perf_counter() - t0) / args.decode_steps
    out = jnp.concatenate(generated, axis=1)
    print(f"decode: {1e3 * dt:.1f} ms/token/batch "
          f"({1e6 * dt / args.batch:.0f} us/token/sequence)")
    print(f"generated ids[0]: {list(map(int, out[0]))}")


if __name__ == "__main__":
    from repro.launch import enable_compile_cache
    enable_compile_cache()
    main()
