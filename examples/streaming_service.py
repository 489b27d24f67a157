"""Streaming training service demo: live ingestion, freshness, crash resume.

    PYTHONPATH=src python examples/streaming_service.py

Two acts:

1. **Freshness** — cold-start a streaming service on a drifting synthetic
   stream, splice a burst of probe events for a (user, item) pair the
   background stream would never teach, and count rounds until the probe
   item shows up in that user's *served* top-k (through a live
   ``BatchingRecommender`` refreshed every round with zero retrace).

2. **Crash / resume** — re-run the same stream with a failure injected at
   an arbitrary event offset and round-edge checkpoints enabled.  The
   resumed trajectory (embedding tables, positive ring, popularity counts,
   stream cursor) is **bit-identical** to the uninterrupted run, because a
   checkpoint captures the complete round input: model state, ring dataset,
   step/event counters, and the stream cursor.
"""
import shutil
import time

import numpy as np

from repro.core import mf
from repro.data import pipeline
from repro.launch.server import BatchingRecommender
from repro.stream.service import StreamingConfig, StreamingTrainer
from repro.stream.sources import ProbeInjector, SyntheticStream

USERS, ITEMS, DIM = 200, 400, 16
ROUNDS, MICRO = 8, 256
PROBE_USER, PROBE_ITEM = 1, ITEMS - 1
CKPT = "/tmp/repro_stream_demo_ckpt"


def make_stream():
    """The demo stream: drifting synthetic base + a probe burst spliced at
    event 600.  Pure in (seed, index), so every run sees the same events."""
    base = SyntheticStream(USERS, ITEMS, seed=0, total=ROUNDS * MICRO,
                           user_drift=0.01, item_drift=0.01)
    return ProbeInjector(base, 600, PROBE_USER, PROBE_ITEM, repeat=24)


def make_trainer(stream, **overrides):
    cfg = mf.MFConfig(num_users=USERS, num_items=ITEMS, emb_dim=DIM,
                      num_negatives=16, lr=0.2, backend="fused",
                      sampler="popularity")
    scfg = StreamingConfig(capacity=32, micro_batch=MICRO,
                           steps_per_round=16, batch_size=128,
                           recency=0.5, seed=0, **overrides)
    return StreamingTrainer(cfg, stream, scfg, log=lambda *_: None)


def act_one_freshness():
    print("=== act 1: freshness — ingest to served top-k ===")
    trainer = make_trainer(make_stream())
    server = BatchingRecommender(trainer.state, 10, max_wait_ms=0.5)
    trainer.recommender = server

    t_probe = served_round = None
    while trainer.run(rounds=1):
        s = trainer.last_round_stats
        if t_probe is None and trainer.events > 600:
            t_probe = time.perf_counter()        # probe burst just ingested
        mark = ""
        if t_probe is not None and served_round is None:
            if PROBE_ITEM in server.recommend(PROBE_USER).tolist():
                served_round, mark = s["round"], "  <- probe item served"
        print(f"round {s['round']}: loss {s['loss']:.4f}, "
              f"train {1e3 * s['train_s']:.0f} ms{mark}")
    print(f"window traces: {trainer.executor.trace_counter.count} "
          f"(one compiled program across {trainer.rounds} rounds)")
    if served_round is not None:
        print(f"freshness: probe served {time.perf_counter() - t_probe:.2f} s "
              f"after ingestion (round {served_round})")
    server.stop()
    return trainer


def act_two_crash_resume(reference):
    print("\n=== act 2: crash at event 1000, resume from checkpoint ===")
    shutil.rmtree(CKPT, ignore_errors=True)
    trainer = make_trainer(make_stream(), ckpt_dir=CKPT, ckpt_every=1,
                           fail_at_event=1000)
    trainer.log = print
    trainer.run()                    # crashes once, restores, finishes
    print(f"restarts: {trainer.restarts}")

    ref_p, got_p = reference.state.params, trainer.state.params
    for name, a, b in [
            ("user table", ref_p.user_table, got_p.user_table),
            ("item table", ref_p.item_table, got_p.item_table),
            ("positive ring", reference.data.train_pos, trainer.data.train_pos),
            ("popularity", reference.data.item_weights,
             trainer.data.item_weights)]:
        same = bool(np.array_equal(np.asarray(a), np.asarray(b)))
        print(f"  {name:13s} bit-identical: {same}")
        assert same, f"{name} diverged after resume"
    print("resumed trajectory is bit-identical to the uninterrupted run")
    shutil.rmtree(CKPT, ignore_errors=True)


def main():
    reference = act_one_freshness()
    act_two_crash_resume(reference)


if __name__ == "__main__":
    main()
