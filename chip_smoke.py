"""Chip smoke test: train the paper's MF model with the HEAT step and serve
top-k from it on a TPU, each result checked against a reference.

    python chip_smoke.py              # one chip: the phases below
    python chip_smoke.py --chips 4    # four chips: sharded training only

One chip, in order:

1. device check: JAX's default backend must be a TPU;
2. 48 steps of ``trainer.train_mf`` on ``configs/heat_mf.MF_100M``
   (400k users x 400k items, K=128, n=64 negatives, fp32 tables) with the
   default engine, batch 1024, 16 steps per dispatch window;
3. the same run with the Pallas kernel engine (``MF_100M_PALLAS``), whose
   compiled window must hold the CCL and gather-FMA kernels, compared step
   by step and table by table with the default engine;
4. one window with int8 tables, whose compiled window must hold the
   gather-dequant kernel;
5. top-10 serving through ``BatchingRecommender`` on the trained tables,
   answering concurrent requests through the top-k scan kernel, checked
   against ``mf.topk_all_items`` over the whole catalog at once; prints the
   chunks the scan merged out of those it scanned.

Four chips: the default-engine run on a 4-way ``data`` mesh and on a
2 x 2 (``data``, ``model``) mesh, compared with a one-device run.

Any failed check exits non-zero.  Without a TPU the script exits non-zero
before any phase and prints no result.  The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

SRC = Path(__file__).resolve().parent / "src"

SEED = 0
BATCH = 1024
STEPS_PER_WINDOW = 16
STEPS = 3 * STEPS_PER_WINDOW
TOPK = 10
REQUESTS = 64              # concurrent top-k requests
SERVE_BATCH = 32           # BatchingRecommender.max_batch
ITEM_CHUNK = 50_000        # catalog chunk of the server's running top-k

# Default engine vs Pallas engine.  The kernels multiply in f32.  Where XLA's
# default f32 precision on a TPU rounds the default engine's dot operands to
# bf16 (a relative error of 2^-9 per product, about 2e-3 on one cosine),
# averaging over the 1024 x 65 cosines of a step moves a step's mean loss by
# about 1e-4; 1e-3 is ten times that and far below what a dropped term does
# (the negative term alone is about 0.03).  On a TPU v5e the per-example
# contractions of both engines ran in f32 and the losses agreed to about
# 1e-7.  The loss barely moves in 48 steps, so the gradients are checked on
# the tables: the two runs' table updates (final minus initial) may differ
# by 5% of their norm; a wrong or missing gradient term is O(100%).
ENGINE_LOSS_ATOL = 1e-3
ENGINE_DELTA_RTOL = 5e-2
# int8 tables vs fp32: the per-row absmax quantization moves each cosine by
# well under 1%; 1e-2 on a step's mean loss bounds that.
INT8_LOSS_ATOL = 1e-2
# Sharded vs one device (the tests/test_multidevice.py contract): the same
# draws and per-row math, only cross-device reduction order differs.
SHARD_ATOL = 1e-5

PALLAS_KERNELS = ("_stats_kernel", "_bwd_kernel", "_gather_fma_kernel")
INT8_KERNELS = ("_gather_dequant_kernel",)

_T0 = time.perf_counter()


def say(msg: str) -> None:
    print(f"[{time.perf_counter() - _T0:7.1f}s] {msg}", flush=True)


def check(ok, what: str) -> None:
    """Stop the run with a non-zero exit unless ``ok``."""
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def train(cfg, ds, steps, *, mesh=None):
    """``trainer.train_mf`` at the smoke shape; returns (state, losses)."""
    from repro.train import trainer
    state, losses = trainer.train_mf(
        cfg, ds, steps, batch_size=BATCH, seed=SEED,
        steps_per_dispatch=STEPS_PER_WINDOW, mesh=mesh, log=say)
    losses = np.asarray(losses)
    check(losses.shape == (steps,) and np.isfinite(losses).all(),
          f"{cfg.backend}/{cfg.table_format} losses not finite: {losses}")
    return state, losses


def batch_fn(cfg, ds):
    from repro.data import pipeline
    dds = pipeline.device_cf_dataset(ds)
    return lambda step: pipeline.cf_batch_device(dds, SEED, step, BATCH,
                                                 cfg.history_len)


def window_text(cfg, ds) -> tuple[str, str]:
    """(StableHLO, compiled HLO) of one dispatch window of ``cfg``, as
    ``train_mf`` runs it (scanned ``heat_train_step`` over in-window
    batches)."""
    import jax
    import jax.numpy as jnp
    from repro.core import mf
    body = mf.make_scan_body(cfg, batch_fn(cfg, ds), SEED)
    state = jax.eval_shape(lambda: mf.init_mf(jax.random.PRNGKey(SEED), cfg))
    window = jax.jit(lambda st, s0: jax.lax.scan(
        body, st, s0 + jnp.arange(STEPS_PER_WINDOW, dtype=jnp.int32)),
        donate_argnums=(0,))
    lowered = window.lower(state, jax.ShapeDtypeStruct((), jnp.int32))
    return lowered.as_text(), lowered.compile().as_text()


def check_kernels(cfg, ds, names) -> None:
    """The window holds each named Pallas kernel, compiled to the TPU.  The
    compiled HLO keeps no kernel names (each is an opaque ``tpu_custom_call``
    body), so names are read from the lowered module's ``kernel_name``
    attributes and the compiled calls are counted."""
    lowered, compiled = window_text(cfg, ds)
    missing = [n for n in names if f'kernel_name = "{n}"' not in lowered]
    calls = compiled.count('custom_call_target="tpu_custom_call"')
    check(not missing and calls >= len(names),
          f"{cfg.backend}/{cfg.table_format} window: kernels {missing} not "
          f"lowered, {calls} tpu_custom_call compiled")
    say(f"kernels: {cfg.backend}/{cfg.table_format} window compiled with "
        f"{calls} tpu_custom_call; lowered kernels {', '.join(names)}")


def make_replay(cfg, ds, steps):
    """state -> per-step losses of the run's own batches and negatives
    (same seed and steps), evaluated at ``state`` without updating it."""
    import jax
    import jax.numpy as jnp
    from repro.core import mf
    body = mf.make_scan_body(cfg, batch_fn(cfg, ds), SEED)
    fn = jax.jit(lambda st: jax.lax.map(
        lambda s: body(st, s)[1], jnp.arange(steps, dtype=jnp.int32)))
    return lambda state: np.asarray(fn(state))


def check_falls(name, replay, state, losses) -> None:
    """At 400k users a 48-step run meets each user about 0.1 times, so the
    loss of fresh batches moves less than batch noise.  Training must
    lower the loss of the batches it trained on: those batches, replayed at
    the final tables, must score below the losses recorded when they ran."""
    again = replay(state)
    windows = losses.reshape(-1, STEPS_PER_WINDOW).mean(axis=1)
    say(f"train[{name}]: loss first {losses[0]:.6f} last {losses[-1]:.6f}; "
        f"window means {' '.join(f'{w:.6f}' for w in windows)}; "
        f"same batches at final tables {again.mean():.6f} < "
        f"{losses.mean():.6f} recorded")
    check(np.isfinite(again).all() and again.mean() < losses.mean(),
          f"{name}: training did not lower the loss of its own batches")


def rel_delta_err(init, a, b) -> float:
    """||a - b|| / ||b - init||: how far two runs' updates disagree."""
    import jax.numpy as jnp
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b - init))


def phase_engines(cfg, cfg_pallas, ds):
    """Default engine, then the Pallas engine on the same seed and steps."""
    import jax
    from repro.core import mf
    init = mf.init_mf(jax.random.PRNGKey(SEED), cfg).params
    replay = make_replay(cfg, ds, STEPS)

    s_f, l_f = train(cfg, ds, STEPS)
    check_falls("default engine", replay, s_f, l_f)

    check_kernels(cfg_pallas, ds, PALLAS_KERNELS)
    s_p, l_p = train(cfg_pallas, ds, STEPS)
    check_falls("pallas engine", replay, s_p, l_p)

    loss_err = float(np.max(np.abs(l_p - l_f)))
    d_user = rel_delta_err(init.user_table, s_p.params.user_table,
                           s_f.params.user_table)
    d_item = rel_delta_err(init.item_table, s_p.params.item_table,
                           s_f.params.item_table)
    say(f"parity: max |loss pallas - default| {loss_err:.3e} "
        f"(limit {ENGINE_LOSS_ATOL:g}); table update error user "
        f"{d_user:.3e} item {d_item:.3e} (limit {ENGINE_DELTA_RTOL:g})")
    check(loss_err <= ENGINE_LOSS_ATOL, "engine loss parity")
    check(d_user <= ENGINE_DELTA_RTOL and d_item <= ENGINE_DELTA_RTOL,
          "engine table-update parity")
    return s_f, l_f


def phase_int8(cfg_pallas, ds, l_f):
    """One window with int8 tables on the kernel engine."""
    cfg8 = dataclasses.replace(cfg_pallas, table_format="int8")
    check_kernels(cfg8, ds, INT8_KERNELS)
    _, l8 = train(cfg8, ds, STEPS_PER_WINDOW)
    err = float(np.max(np.abs(l8 - l_f[:STEPS_PER_WINDOW])))
    say(f"int8: {STEPS_PER_WINDOW} steps, loss first {l8[0]:.6f} last "
        f"{l8[-1]:.6f}; max |int8 - fp32| {err:.3e} "
        f"(limit {INT8_LOSS_ATOL:g})")
    check(err <= INT8_LOSS_ATOL, "int8 loss within the fp32 run's")


def same_topk(params, users, got, want) -> bool:
    """Equal ids, or (on a tie in the chip's scores) server ids whose exact
    cosines equal the reference's to bf16 resolution."""
    if np.array_equal(got, want):
        return True
    u = np.asarray(params.user_table[users], np.float64)
    u /= np.linalg.norm(u, axis=-1, keepdims=True)

    def scores(ids):
        it = np.asarray(params.item_table[ids.reshape(-1)], np.float64)
        it = (it / np.linalg.norm(it, axis=-1, keepdims=True)).reshape(
            ids.shape + (-1,))
        return np.sort(np.einsum("bk,bjk->bj", u, it), axis=1)

    return bool(np.allclose(scores(got), scores(want), atol=4e-3, rtol=0))


def phase_serve(state):
    """Concurrent top-k requests through the batching server."""
    import jax.numpy as jnp
    from repro.core import mf
    from repro.kernels import ops
    from repro.kernels.topk_scan import chunk_width
    from repro.launch.server import BatchingRecommender
    num_users = state.params.user_table.shape[0]
    users = np.linspace(0, num_users - 1, REQUESTS).astype(np.int32)
    with BatchingRecommender(state, TOPK, pruner="exact",
                             max_batch=SERVE_BATCH, item_chunk=ITEM_CHUNK,
                             log=say) as server:
        with concurrent.futures.ThreadPoolExecutor(REQUESTS) as pool:
            got = np.stack(list(pool.map(
                lambda u: server.recommend(int(u), timeout=300.0), users)))
        stats = server.stats
    want = np.concatenate([
        np.asarray(mf.topk_all_items(state.params,
                                     jnp.asarray(users[s:s + SERVE_BATCH]),
                                     TOPK))
        for s in range(0, REQUESTS, SERVE_BATCH)])
    exact = int(np.sum(np.all(got == want, axis=1)))
    say(f"serve: {REQUESTS} concurrent top-{TOPK} requests in "
        f"{stats['device_calls']} device calls ({stats['traces']} trace); "
        f"{exact}/{REQUESTS} rows equal to mf.topk_all_items")
    check(got.shape == (REQUESTS, TOPK), f"server answer shape {got.shape}")
    check(same_topk(state.params, users, got, want),
          "server top-k differs from mf.topk_all_items")
    check(stats["traces"] == 1 and stats["status"] == "ok",
          f"server stats {stats}")
    items = state.params.item_table
    u = state.params.user_table[jnp.asarray(users[:SERVE_BATCH])]
    _, merged = ops.topk_scan(u, items, None, TOPK, similarity="cosine",
                              item_chunk=ITEM_CHUNK)
    chunks = -(-items.shape[0] // chunk_width(ITEM_CHUNK))
    say(f"serve: the top-k scan merged {int(merged)} of {chunks} chunks "
        f"({int(merged) / chunks:.2%}) for {SERVE_BATCH} users")


def run_one_chip(cfg, cfg_pallas, ds) -> None:
    s_f, l_f = phase_engines(cfg, cfg_pallas, ds)
    phase_int8(cfg_pallas, ds, l_f)
    phase_serve(s_f)


def run_sharded(cfg, ds) -> None:
    """Sharded training on four devices vs the one-device run."""
    from repro.launch.mesh import make_data_mesh, make_host_mesh
    s_1, l_1 = train(cfg, ds, STEPS)
    ref = [np.asarray(t) for t in (s_1.params.user_table,
                                   s_1.params.item_table)]
    for name, mesh in (("data=4", make_data_mesh(4)),
                       ("data=2 x model=2", make_host_mesh(2, 2))):
        s, l = train(cfg, ds, STEPS, mesh=mesh)
        user, item = s.params.user_table, s.params.item_table
        spans = [len(t.sharding.device_set) for t in (user, item)]
        rows = [t.addressable_shards[0].data.shape[0] for t in (user, item)]
        loss_err = float(np.max(np.abs(l - l_1)))
        tab_err = max(float(np.max(np.abs(np.asarray(t) - r)))
                      for t, r in zip((user, item), ref))
        fp = [float(np.sum(np.square(np.asarray(t, np.float64))))
              for t in (user, item)]
        say(f"sharded[{name}]: tables span {spans} devices, shard rows "
            f"user {rows[0]} item {rows[1]}; max |loss - 1 device| "
            f"{loss_err:.3e}, max |table - 1 device| {tab_err:.3e} "
            f"(limit {SHARD_ATOL:g}); sum of squares user {fp[0]:.6f} "
            f"item {fp[1]:.6f}")
        check(spans == [4, 4], f"{name}: state spans {spans} devices")
        data, model = mesh.shape["data"], mesh.shape["model"]
        check(rows == [cfg.num_users // data, cfg.num_items // model],
              f"{name}: shard rows {rows}")
        check(loss_err <= SHARD_ATOL and tab_err <= SHARD_ATOL,
              f"{name}: sharded run differs from the one-device run")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: train, kernels, int8 and serving on one chip; "
                         "4: sharded training on four chips only")
    args = ap.parse_args()
    if not (SRC / "repro").is_dir():
        raise SystemExit(f"chip_smoke: no repro package under {SRC}")
    sys.path.insert(0, str(SRC))
    from repro.launch import enable_compile_cache
    enable_compile_cache()

    import jax
    backend = jax.default_backend()
    devices = jax.devices()
    say(f"device: platform={devices[0].platform} "
        f"kind={devices[0].device_kind} count={len(devices)}")
    if backend != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX's default backend "
                         f"is {backend!r}")
    check(len(devices) >= args.chips,
          f"--chips {args.chips} needs {args.chips} devices, found "
          f"{len(devices)}")

    from repro.configs.heat_mf import MF_100M, MF_100M_PALLAS
    from repro.data import pipeline
    ds = pipeline.synth_cf_dataset(MF_100M.num_users, MF_100M.num_items,
                                   seed=SEED)
    say(f"data: {ds.num_users} users x {ds.num_items} items, "
        f"{int((ds.train_pos >= 0).sum())} train interactions")
    if args.chips == 4:
        run_sharded(MF_100M, ds)
    else:
        run_one_chip(MF_100M, MF_100M_PALLAS, ds)
    peak = devices[0].memory_stats()["peak_bytes_in_use"]
    say(f"memory: device 0 peak_bytes_in_use {peak}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
