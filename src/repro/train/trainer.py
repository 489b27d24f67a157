"""Training loops with checkpoint/restart, failure injection, and elastic
resume — for both the LM zoo and the paper's own MF-CF model.

Fault-tolerance model (DESIGN.md §5):
  - step-granular atomic checkpoints (train/checkpoint.py), data batches are
    pure functions of (seed, step) -> bit-exact resume;
  - ``fail_at_step`` injects a crash (tests + demos); the driver loop catches
    ``SimulatedFailure``/restart-able errors, restores the latest checkpoint
    and continues — the single-process stand-in for a pod-scheduler restart;
  - elastic: restore() lays checkpoints out on whatever mesh is active now;
  - stragglers: synchronous SPMD has no per-step stragglers inside a pod; the
    deferred aggregator sync (m-step flush) and the compressed cross-pod
    psum bound the damage of slow links; a hard-timeout -> restart policy is
    the cluster-level fallback (documented, not simulatable single-process).
"""
from __future__ import annotations

import contextlib
import dataclasses
from functools import partial
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import tracing
from repro.analysis.sanitize import TraceCounter
from repro.core import mf, samplers
from repro.core import mf_distributed as mfd
from repro.core.engine import StepEngine, resolve_engine
from repro.data import pipeline
from repro.distributed import sharding as shd
from repro.models import lm
from repro.models.config import ArchConfig
from repro.optim.optimizers import Optimizer, get_optimizer
from repro.train import checkpoint as ckpt


class SimulatedFailure(RuntimeError):
    """Injected node failure (tests / fault-tolerance demos)."""


@dataclasses.dataclass
class TrainerConfig:
    """LM trainer knobs (steps, lr, checkpointing, failure injection)."""
    steps: int = 100
    lr: float = 1e-3
    batch_size: int = 8
    seq_len: int = 64
    seed: int = 0
    optimizer: str = "adamw"
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 50
    log_every: int = 10
    fail_at_step: Optional[int] = None      # failure injection
    max_restarts: int = 2
    grad_accum: int = 1
    fixed_batch: bool = False               # overfit one batch (tests/demos)
    steps_per_dispatch: int = 1             # >1: scanned EpochExecutor windows
    mesh: Optional[Any] = None              # device mesh; None = active mesh


class LMTrainState(NamedTuple):
    """The LM training carry: params, optimizer state, tile, step."""
    params: Any
    opt_state: Any
    tile: Any                   # id-only samplers.TileState or None
    step: jax.Array


def make_lm_train_step_raw(cfg: ArchConfig, opts: lm.TrainOptions,
                           optimizer: Optimizer, lr: float,
                           grad_accum: int = 1) -> Callable:
    """Traceable (state, batch, rng) -> (state, loss) — the un-jitted LM step,
    consumable both standalone (``make_lm_train_step`` jits it) and as the
    body of an ``EpochExecutor`` dispatch window (scanned, so it must not
    carry its own jit boundary).

    grad_accum > 1 runs a microbatch scan, accumulating gradients — the
    deferred-synchronization discipline of paper §4.5 applied to the dense
    parameters (one optimizer update / all-reduce per accumulation window).
    """

    def loss_fn(params, batch, rng, tile):
        loss, new_tile = lm.forward_train(params, batch, cfg, opts, rng, tile)
        return loss, new_tile

    def one_micro(params, tile, batch, rng):
        (loss, new_tile), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch, rng, tile)
        return loss, grads, new_tile

    def step_fn(state: LMTrainState, batch, rng):
        if grad_accum == 1:
            loss, grads, tile = one_micro(state.params, state.tile, batch, rng)
        else:
            micro = jax.tree.map(
                lambda x: x.reshape((grad_accum, -1) + x.shape[1:]), batch)

            def body(carry, xs):
                g_sum, tile_c, i = carry
                mb = xs
                l, g, tile_c = one_micro(state.params, tile_c, mb,
                                         jax.random.fold_in(rng, i))
                g_sum = jax.tree.map(jnp.add, g_sum, g)
                return (g_sum, tile_c, i + 1), l

            zeros = jax.tree.map(jnp.zeros_like, state.params)
            (g_sum, tile, _), losses = jax.lax.scan(
                body, (zeros, state.tile, jnp.zeros((), jnp.int32)), micro)
            grads = jax.tree.map(lambda g: g / grad_accum, g_sum)
            loss = jnp.mean(losses)
        new_params, new_opt = optimizer.update(grads, state.opt_state,
                                               state.params, lr)
        return LMTrainState(new_params, new_opt, tile, state.step + 1), loss

    return step_fn


def make_lm_train_step(cfg: ArchConfig, opts: lm.TrainOptions, optimizer: Optimizer,
                       lr: float, grad_accum: int = 1) -> Callable:
    """Jitted (state, batch, rng) -> (state, loss) with donated state."""
    return jax.jit(make_lm_train_step_raw(cfg, opts, optimizer, lr, grad_accum),
                   donate_argnums=(0,))


# ----------------------------------------------------------------------------
# Device-resident epoch executor: K-step scanned dispatch windows
# ----------------------------------------------------------------------------

class EpochExecutor:
    """Runs the steady-state training loop as ``lax.scan`` over K-step
    dispatch windows with donated carry (the §3.1 fix applied to the *loop*:
    one Python->XLA dispatch, zero host->device batch copies, and one
    blocking sync per window instead of per step).

    ``body(state, step) -> (state, loss)`` must be traceable with a traced
    step index — it derives both the batch and the per-step rng from
    ``step``, so a window is a pure function of ``(state, start)`` and the
    (seed, step) restart contract is unchanged.  Windows may be truncated
    (end of run, checkpoint boundary, injected failure), so checkpointing
    and resume always land on window edges; each distinct length compiles
    once and is cached.

    ``state_shardings`` (a pytree of NamedShardings mirroring the carry,
    e.g. ``MFShardingPlan.state_shardings``) turns the executor multi-device:
    windows are jitted with the carry pinned to those shardings on the way in
    *and* out, so the sharded state is donated window-to-window with zero
    resharding, and the per-window loss array lands replicated
    (``scalar_sharding``) for the edge sync.

    Every window trace increments ``trace_counter``
    (:class:`repro.analysis.sanitize.TraceCounter`): a steady-state run
    traces once per *distinct window length* and never again, so
    ``trace_counter.check(budget)`` turns a silent recompile-per-dispatch
    regression into a hard failure (``trace_budget`` arms the check on the
    counter itself).
    """

    def __init__(self, body: Callable, steps_per_dispatch: int, *,
                 state_shardings=None, scalar_sharding=None,
                 trace_budget: Optional[int] = None):
        self.body = body
        self.steps_per_dispatch = max(int(steps_per_dispatch), 1)
        self.state_shardings = state_shardings
        self.scalar_sharding = scalar_sharding
        self.trace_counter = TraceCounter("epoch_executor.window",
                                          trace_budget)
        self._windows: dict[int, Callable] = {}

    def _compiled(self, length: int) -> Callable:
        fn = self._windows.get(length)
        if fn is None:
            def run_window(state, start):
                steps = start + jnp.arange(length, dtype=jnp.int32)
                return jax.lax.scan(self.body, state, steps)
            kw = {}
            if self.state_shardings is not None:
                kw = dict(
                    in_shardings=(self.state_shardings, self.scalar_sharding),
                    out_shardings=(self.state_shardings,
                                   self.scalar_sharding))
            fn = jax.jit(self.trace_counter.wrap(run_window),
                         donate_argnums=(0,), **kw)
            self._windows[length] = fn
        return fn

    def run(self, state, start: int, length: int):
        """Dispatch one [start, start+length) window; returns
        (new_state, (length,) device loss array) — the only sync the driver
        does is reading that array back at the window edge.

        The start index goes up via ``jax.device_put`` (an *explicit*
        transfer): ``jnp.asarray(start)`` counts as implicit and would trip
        ``repro.analysis.sanitize``'s transfer guard on every dispatch."""
        fn = self._compiled(length)
        with tracing.span(tracing.TRAIN_DISPATCH):
            return fn(state, jax.device_put(np.int32(start)))


def _window_length(step: int, stop: int, k: int, ckpt_every: int,
                   fail_at_step: Optional[int]) -> int:
    """Next dispatch-window length: at most ``k`` steps, truncated so window
    edges land exactly on the run end, the checkpoint schedule, and any armed
    failure injection (the failure then fires *between* windows, where state
    is well-defined and restorable)."""
    length = min(k, stop - step)
    if ckpt_every:
        length = min(length, ckpt_every - step % ckpt_every)
    if fail_at_step is not None and step < fail_at_step:
        length = min(length, fail_at_step - step)
    return length


def run_window(executor: EpochExecutor, state, step: int, stop: int,
               ckpt_every: int = 0, fail_at_step: Optional[int] = None):
    """One truncated dispatch window + its edge sync — the single definition
    of the window contract every driver (train_lm / train_mf / the streaming
    service's train-on-recent rounds) runs on.
    Returns (new_state, host loss array, length)."""
    length = _window_length(step, stop, executor.steps_per_dispatch,
                            ckpt_every, fail_at_step)
    state, window = executor.run(state, step, length)
    with tracing.span(tracing.TRAIN_READBACK):
        losses = np.asarray(window)
    return state, losses, length


_run_window = run_window        # internal callers predate the public name


def init_lm_state(rng: jax.Array, cfg: ArchConfig, opts: lm.TrainOptions,
                  optimizer: Optimizer, dtype=jnp.float32) -> LMTrainState:
    """Fresh LMTrainState from the arch config and optimizer."""
    kp, kt = jax.random.split(rng)
    params = lm.init_params(kp, cfg, dtype)
    tile = (samplers.id_tile_init(kt, cfg.vocab, cfg.heat.tile_size)
            if (opts.loss == "heat" and cfg.heat.enabled and cfg.heat.tile_size)
            else None)
    return LMTrainState(params, optimizer.init(params), tile,
                        jnp.zeros((), jnp.int32))


def train_lm(cfg: ArchConfig, opts: lm.TrainOptions, tcfg: TrainerConfig,
             extras_spec: Optional[dict] = None,
             log: Callable[[str], None] = print) -> tuple[LMTrainState, list]:
    """End-to-end LM training driver with restart-on-failure.

    ``tcfg.steps_per_dispatch > 1`` runs the steady state through the
    :class:`EpochExecutor` (batches sampled in-scan, one dispatch + one loss
    sync per window).  Either way the driver never blocks on a per-step
    ``float(loss)``: losses stay on device and are read back at window /
    ``log_every`` boundaries only.

    ``tcfg.mesh`` installs a device mesh for the run (models' logical-axis
    constraints resolve against it and batches are pinned to the data axes);
    with no explicit mesh, an already-active ``shd`` mesh is honored the same
    way — the launcher's ``--mesh`` path.
    """
    if tcfg.mesh is not None and shd.get_mesh() is not tcfg.mesh:
        with shd.use_mesh(tcfg.mesh):
            return train_lm(cfg, opts, dataclasses.replace(tcfg, mesh=None),
                            extras_spec, log)
    data_mesh = shd.active_mesh()

    def shard_batch(batch):
        """Pin batch rows to the data axes (no-op without a usable mesh)."""
        if data_mesh is None:
            return batch
        return {k: shd.constrain(v, shd.batch_spec(*(None,) * (v.ndim - 1)))
                for k, v in batch.items()}

    optimizer = get_optimizer(tcfg.optimizer)
    rng = jax.random.PRNGKey(tcfg.seed)
    state = init_lm_state(rng, cfg, opts, optimizer)
    start = 0

    if tcfg.ckpt_dir and ckpt.latest_step(tcfg.ckpt_dir) is not None:
        state, start, _ = ckpt.restore(tcfg.ckpt_dir, state)
        log(f"[trainer] resumed from step {start}")

    k = max(1, tcfg.steps_per_dispatch)
    raw_step = make_lm_train_step_raw(cfg, opts, optimizer, tcfg.lr,
                                      tcfg.grad_accum)
    if k > 1:
        def body(state, step):
            b_step = jnp.zeros_like(step) if tcfg.fixed_batch else step
            batch = pipeline.lm_batch(b_step, tcfg.batch_size, tcfg.seq_len,
                                      cfg.vocab, tcfg.seed, extras_spec)
            return raw_step(state, shard_batch(batch),
                            jax.random.fold_in(rng, step))
        executor = EpochExecutor(body, k)
    else:
        step_fn = jax.jit(lambda s, b, r: raw_step(s, shard_batch(b), r),
                          donate_argnums=(0,))

    restarts = 0
    losses: list = []
    step = start
    while step < tcfg.steps:
        try:
            if tcfg.fail_at_step is not None and step == tcfg.fail_at_step \
                    and restarts == 0:
                raise SimulatedFailure(f"injected failure at step {step}")
            if k > 1:
                state, window, length = _run_window(
                    executor, state, step, tcfg.steps,
                    tcfg.ckpt_every if tcfg.ckpt_dir else 0,
                    tcfg.fail_at_step if restarts == 0 else None)
                losses.extend(window.tolist())
                if tcfg.log_every:
                    for i in range(step, step + length):
                        if i % tcfg.log_every == 0:
                            log(f"[trainer] step {i} loss "
                                f"{window[i - step]:.4f}")
                step += length
            else:
                batch = pipeline.lm_batch(0 if tcfg.fixed_batch else step,
                                          tcfg.batch_size, tcfg.seq_len,
                                          cfg.vocab, tcfg.seed, extras_spec)
                state, loss = step_fn(state, batch,
                                      jax.random.fold_in(rng, step))
                losses.append(loss)                # device scalar — no sync
                if tcfg.log_every and step % tcfg.log_every == 0:
                    log(f"[trainer] step {step} loss "
                        f"{float(loss):.4f}")  # heatlint: disable=HL107 -- log_every-gated readback, not per-step
                step += 1
            if tcfg.ckpt_dir and step % tcfg.ckpt_every == 0:
                ckpt.save(tcfg.ckpt_dir, step, state)
        except SimulatedFailure as e:
            restarts += 1
            if restarts > tcfg.max_restarts or not tcfg.ckpt_dir:
                raise
            log(f"[trainer] {e} -> restoring latest checkpoint")
            if ckpt.latest_step(tcfg.ckpt_dir) is not None:
                state, step, _ = ckpt.restore(tcfg.ckpt_dir, state)
            else:
                state = init_lm_state(rng, cfg, opts, optimizer)
                step = 0
    if losses and not isinstance(losses[0], float):
        # per-step path: one bulk readback instead of a float() per step
        losses = np.asarray(jnp.stack(losses)).tolist()
    return state, losses


# ----------------------------------------------------------------------------
# MF / CF trainer (the paper's own training loop)
# ----------------------------------------------------------------------------

def train_mf(cfg: mf.MFConfig, ds: pipeline.CFDataset, steps: int, *,
             batch_size: int = 256, seed: int = 0,
             engine: Optional[StepEngine] = None,
             item_weights=None,
             ckpt_dir: Optional[str] = None,
             ckpt_every: int = 200, fail_at_step: Optional[int] = None,
             steps_per_dispatch: int = 1,
             mesh=None,
             log: Callable[[str], None] = print):
    """HEAT CF training (Fig. 3 loop) with the same fault-tolerance contract.

    ``engine`` picks the execution backend (core/engine.py); by default it is
    resolved from ``cfg.backend`` / ``cfg.update_impl`` / ``cfg.sampler``.
    ``item_weights`` (optional (I,)) feeds the ``popularity`` sampler; when
    omitted and the resolved sampler is ``popularity``, the dataset's own
    interaction counts (``DeviceCFDataset.item_weights``) are used.

    ``steps_per_dispatch=K`` (> 1) runs the steady state device-resident: the
    dataset is uploaded once (``pipeline.device_cf_dataset``), batches are
    sampled in-scan (``pipeline.cf_batch_device``), and the
    :class:`EpochExecutor` dispatches K steps at a time, syncing losses only
    at window edges.  Batches are bit-identical to the per-step loop's, so
    both paths (and any K) produce the same trajectory, and checkpoints /
    injected failures land on window edges with the same (seed, step)
    restart guarantee.

    ``mesh`` (default: the active ``shd`` mesh when it has more than one
    device) runs the same loop *sharded*: the state is placed per
    ``mf_distributed.make_sharding_plan`` (user rows over the data axes, item
    rows over ``model``), batches sampled in-scan are pinned to the data axes,
    and the executor's windows carry the sharded state donated end to end.
    Sampling is sharding-invariant (partitionable threefry), so the sharded
    trajectory tracks the single-device one exactly up to cross-device
    float-reduction order (tests/test_multidevice.py quantifies it).
    """
    if engine is None:
        engine = resolve_engine(cfg)
    if item_weights is None and engine.sampler_name == "popularity":
        item_weights = pipeline.device_cf_dataset(ds).item_weights
    mesh = mesh if mesh is not None else shd.active_mesh()
    plan = mfd.make_sharding_plan(cfg, mesh) if mesh is not None else None
    state_shardings = plan.state_shardings if plan is not None else None
    rng = jax.random.PRNGKey(seed)

    def init_state():
        s = mf.init_mf(rng, cfg)
        return plan.place_state(s) if plan is not None else s

    state = init_state()
    k = max(1, steps_per_dispatch)
    if k > 1:
        dds = pipeline.device_cf_dataset(ds)

        def batch_fn(step):
            b = pipeline.cf_batch_device(dds, seed, step, batch_size,
                                         cfg.history_len)
            return plan.constrain_batch(b) if plan is not None else b

        body = mf.make_scan_body(cfg, batch_fn, seed, engine=engine,
                                 item_weights=item_weights)
        executor = EpochExecutor(
            body, k, state_shardings=state_shardings,
            scalar_sharding=plan.scalar_sharding if plan else None)
    else:
        raw_step = partial(mf.heat_train_step, cfg=cfg, engine=engine,
                           item_weights=item_weights)
        if plan is not None:
            def sharded_step(state, batch, rng):
                return raw_step(state, plan.constrain_batch(batch), rng)
            step_fn = jax.jit(
                sharded_step,
                in_shardings=(state_shardings, None, None),
                out_shardings=(state_shardings, plan.scalar_sharding),
                donate_argnums=(0,))
        else:
            step_fn = jax.jit(raw_step, donate_argnums=(0,))
    start = 0
    if ckpt_dir and ckpt.latest_step(ckpt_dir) is not None:
        state, start, _ = ckpt.restore(ckpt_dir, state,
                                       shardings=state_shardings)
        log(f"[mf] resumed from step {start}")

    losses = []
    step, restarts = start, 0
    # Windows trace lazily on first dispatch; the mesh must be installed then
    # so the step's sharding constraints (shd.constrain / shd.replicated)
    # resolve against it.
    run_ctx = (shd.use_mesh(mesh) if plan is not None
               else contextlib.nullcontext())
    with run_ctx:
        while step < steps:
            try:
                if fail_at_step is not None and step == fail_at_step \
                        and restarts == 0:
                    raise SimulatedFailure(f"injected failure at step {step}")
                if k > 1:
                    state, window, length = _run_window(
                        executor, state, step, steps,
                        ckpt_every if ckpt_dir else 0,
                        fail_at_step if restarts == 0 else None)
                    losses.extend(window.tolist())          # window-edge sync
                    step += length
                else:
                    batch = pipeline.cf_batch(ds, step, batch_size,
                                              cfg.history_len, seed)
                    state, loss = step_fn(state, batch,
                                          jax.random.fold_in(rng, step))
                    losses.append(loss)        # device scalar — no sync
                    step += 1
                if ckpt_dir and step % ckpt_every == 0:
                    ckpt.save(ckpt_dir, step, state)
            except SimulatedFailure as e:
                restarts += 1
                if restarts > 2 or not ckpt_dir:
                    raise
                log(f"[mf] {e} -> restoring")
                if ckpt.latest_step(ckpt_dir) is not None:
                    state, step, _ = ckpt.restore(ckpt_dir, state,
                                                  shardings=state_shardings)
                else:       # failed before the first checkpoint: start over
                    state, step = init_state(), 0
    if losses and not isinstance(losses[0], float):
        # per-step path: one bulk readback instead of a float() per step
        losses = np.asarray(jnp.stack(losses)).tolist()
    return state, losses
