"""Training traffic: the program's own steady state, ``trainer.run_window``
over an ``EpochExecutor`` of ``mf.make_scan_body`` windows whose batches
``pipeline.cf_batch_device`` derives in-scan from a device-resident dataset.

Set-up builds the state from the seed (``bench/traffic/tables.py``), the data
(``bench/traffic/cf_data.py``) and the executor, then runs the first window:
it compiles the window and gives the steps the reference checks.  The run
starts ``refresh_in`` steps before a tile refresh (as a run resumed there
would), so those checked steps include one.  The measured window dispatches
windows back to back, each ended by its loss readback, for ``--seconds``.

The traffic mix file gives ``batch_size``, ``steps_per_window``,
``refresh_in``, the fixed seeds of the data and of the program's batch
stream (the window program is compiled with both, so that every run of a
checkout finds it in the compile cache; the run's ``--seed`` draws the
tables and the tile), ``trace_seconds`` and the ``limits`` of the
comparison.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness, reference
from bench.traffic import cf_data, tables


def mf_config(config: dict):
    from repro.core import mf
    names = {f.name for f in dataclasses.fields(mf.MFConfig)}
    return mf.MFConfig(**{k: v for k, v in config.items() if k in names})


def spec_of(config: dict, traffic: dict) -> reference.MFSpec:
    return reference.MFSpec(
        num_users=config["num_users"], num_items=config["num_items"],
        num_negatives=config["num_negatives"],
        tile_size=config["tile_size"],
        refresh_interval=config["refresh_interval"],
        batch_size=traffic["batch_size"], lr=config["lr"],
        mu=config["mu"], theta=config["theta"])


def dataset(config: dict, traffic: dict):
    d = traffic["data"]
    return cf_data.generate(d["seed"], config["num_users"],
                            config["num_items"], clusters=d["clusters"],
                            columns=d["columns"],
                            candidates=d["candidates"])


def start_step(config: dict, traffic: dict) -> int:
    return config["refresh_interval"] - traffic["refresh_in"]


@dataclasses.dataclass
class Trainer:
    """The set-up's one object: the executor and the state it carries."""
    executor: object
    state: object
    step: int
    observation: reference.MFObservation
    observe_s: float


def initial_tables(config: dict, seed: int):
    """(user, item, tile ids, tile rows) of the run's seed: tables in the
    configuration's format (an int8 table as the program's
    ``QuantizedTable``), tile rows in fp32."""
    from repro.optim import quantization as qz
    ku, ki, kt = tables.keys(jax.random.PRNGKey(seed))
    k, std = config["emb_dim"], config["init_std"]
    tile = tables.tile_ids(kt, config["num_items"], config["tile_size"])
    if config["table_format"] == "int8":
        user = qz.QuantizedTable(*tables.int8_table(ku, config["num_users"],
                                                    k, std))
        item = qz.QuantizedTable(*tables.int8_table(ki, config["num_items"],
                                                    k, std))
        return user, item, tile, tables.initial_rows(
            ki, tile, config["num_items"], k, std, "int8")
    user = tables.normal_table(ku, config["num_users"], k, std)
    item = tables.normal_table(ki, config["num_items"], k, std)
    return user, item, tile, item[tile]


def setup(ctx: harness.Context, train_pos) -> Trainer:
    """Build the state and the executor, and run the first window."""
    from repro.core import mf, samplers
    from repro.data import pipeline
    from repro.train import trainer

    config, traffic = ctx.config, ctx.traffic
    cfg = mf_config(config)
    s0 = start_step(config, traffic)
    user, item, tile, tile_emb = initial_tables(config, ctx.seed)
    tile_emb0 = jnp.copy(tile_emb)
    state = mf.MFState(
        params=mf.MFParams(user, item, None),
        tile=samplers.TileState(tile, tile_emb, jnp.int32(s0)),
        accum=None, step=jnp.int32(s0))
    del user, item, tile, tile_emb
    # the tile sampler reads no item weights
    dds = pipeline.DeviceCFDataset(config["num_users"], config["num_items"],
                                   train_pos, None)
    pseed = traffic["program_seed"]
    batch = traffic["batch_size"]

    def batch_fn(step):
        return pipeline.cf_batch_device(dds, pseed, step, batch,
                                        cfg.history_len)

    executor = trainer.EpochExecutor(
        mf.make_scan_body(cfg, batch_fn, pseed), traffic["steps_per_window"])
    w = traffic["steps_per_window"]
    state, losses, n = trainer.run_window(executor, state, s0, s0 + w)

    t = time.perf_counter()
    obs = observe(state, tile_emb0, config, ctx.seed, losses)
    return Trainer(executor, state, s0 + n, obs,
                   time.perf_counter() - t)


def observe(state, tile_emb0, config: dict, seed: int,
            losses) -> reference.MFObservation:
    """The program's side of the comparison, read from its state after the
    checked steps: change norms against the seed's initial tables (drawn
    again block by block), and the integer leaves."""
    ku, ki, _ = tables.keys(jax.random.PRNGKey(seed))
    k, std, fmt = config["emb_dim"], config["init_std"], config["table_format"]
    p = state.params
    d_tile = state.tile.tile_emb - tile_emb0
    du, di, dt, ids, tstep, step = jax.device_get((
        tables.change_norm(p.user_table, ku, k, std, fmt),
        tables.change_norm(p.item_table, ki, k, std, fmt),
        jnp.sqrt(jnp.sum(d_tile * d_tile)), state.tile.tile_ids,
        state.tile.step, state.step))
    return reference.MFObservation(
        losses=np.asarray(losses, np.float64),
        change={"user_table": float(du), "item_table": float(di),
                "tile_emb": float(dt)},
        exact={"tile_ids": np.asarray(ids), "tile_step": int(tstep),
               "step": int(step)})


def reference_observation(ctx: harness.Context, train_pos,
                          lower=None) -> reference.MFObservation:
    """The reference over the same checked steps, from the seed, on the
    rows they touch (``lower``: the control's precision)."""
    config, traffic = ctx.config, ctx.traffic
    spec = spec_of(config, traffic)
    ku, ki, kt = tables.keys(jax.random.PRNGKey(ctx.seed))
    k, std, fmt = config["emb_dim"], config["init_std"], config["table_format"]
    tile = tables.tile_ids(kt, config["num_items"], config["tile_size"])
    s0, w = start_step(config, traffic), traffic["steps_per_window"]
    pseed = traffic["program_seed"]
    user_ids, item_ids = reference.touched_ids(train_pos, tile, jnp.int32(s0),
                                               spec, pseed, s0, w)
    user0 = tables.initial_rows(ku, user_ids, config["num_users"], k, std,
                                fmt)
    item0 = tables.initial_rows(ki, item_ids, config["num_items"], k, std,
                                fmt)
    return reference.mf_window(user_ids, user0, item_ids, item0, tile,
                               jnp.int32(s0), train_pos, spec, pseed, s0, w,
                               lower)


def distinct_rows(train_pos, config: dict, traffic: dict, first: int,
                  steps: int) -> tuple[float, float]:
    """Mean distinct users and distinct positives per step over steps
    [first, first + steps), by the batch law (for the bytes count)."""
    spec = spec_of(config, traffic)
    pseed = traffic["program_seed"]

    @jax.jit
    def count(train_pos, s):
        u, p = reference.batch_ids(train_pos, spec.num_users, spec.num_items,
                                   pseed, s, spec.batch_size)

        def distinct(x):
            x = jnp.sort(x)
            return 1 + jnp.sum(x[1:] != x[:-1])
        return distinct(u), distinct(p)

    take = np.linspace(first, first + steps - 1, num=min(steps, 64))
    us, ps = zip(*(jax.device_get(count(train_pos, jnp.int32(int(s))))
                   for s in take))
    return float(np.mean(us)), float(np.mean(ps))


def run(ctx: harness.Context) -> harness.Outcome:
    from repro.train import trainer

    config, traffic = ctx.config, ctx.traffic
    train_pos = jax.block_until_ready(dataset(config, traffic))
    ctx.log(f"data ready at {time.perf_counter() - ctx.t0:.1f} s")
    tr = setup(ctx, train_pos)
    ctx.log(f"first window and its reading done at "
            f"{time.perf_counter() - ctx.t0:.1f} s")
    w = traffic["steps_per_window"]
    seconds = ctx.window_seconds

    prof = harness.Profiler(ctx.trace)
    state, step, steps, bad = tr.state, tr.step, 0, 0
    tr.state = None
    harness.settle_host()
    setup_s = time.perf_counter() - ctx.t0 - tr.observe_s
    prof.start()
    t_start = time.perf_counter()
    while True:
        with harness.span("run_window"):
            state, losses, n = trainer.run_window(tr.executor, state, step,
                                                  step + w)
        step += n
        steps += n
        bad += int(np.sum(~np.isfinite(losses)))
        elapsed = time.perf_counter() - t_start
        if elapsed >= seconds:
            break
    prof.stop()
    device = harness.device_record(ctx.devices)
    first_measured = tr.step
    del state, tr.executor
    t_ref = time.perf_counter()
    ref = reference_observation(ctx, train_pos)
    ctx.log(f"reference took {time.perf_counter() - t_ref:.1f} s")
    numbers = reference.compare_mf(tr.observation, ref)
    limits = traffic["limits"]
    checks = {k: (v, float(limits[k])) for k, v in numbers.items()}

    batch = traffic["batch_size"]
    counters = {"steps": steps, "batch_size": batch, "window_s": elapsed}
    if ctx.trace:
        du, dp = distinct_rows(train_pos, config, traffic, first_measured,
                               steps)
        counters.update(distinct_users=du, distinct_positives=dp)
    return harness.Outcome(
        correct=harness.within(checks) and bad == 0,
        attempted=steps, failed=bad,
        end_to_end={"setup_s": setup_s,
                    "positives_per_s": steps * batch / elapsed},
        checks=checks, device=device, counters=counters,
        summary=prof.summary)
