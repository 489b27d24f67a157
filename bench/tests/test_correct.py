"""``correct`` at a size a test run holds, on the CPU: the harness's look
for a chip is skipped and the rest of a run is driven with the timed path
sound, broken underneath (a fault planted in the program), or replaced by
the control (the reference one precision lower).  The cells' own limits are
used."""
import time

import jax
import pytest

from bench import control, harness, reference
from bench.traffic import serve, train

TRAIN = dict(num_users=3000, num_items=2000, emb_dim=32, num_negatives=8,
             tile_size=64, refresh_interval=32)
TRAIN_MIX = dict(batch_size=64, steps_per_window=4, refresh_in=2,
                 trace_seconds=1)
SERVE = dict(num_users=5000, num_items=6000, emb_dim=32)
SERVE_MIX = dict(rate_per_s=60.0, check_requests=32, trace_seconds=1)


def _ctx(workload: str, config_over: dict, mix_over: dict, seed: int):
    spec = harness.benchmark()
    cell = harness.find_cell(spec, workload)
    config = dict(harness.load_config(cell["config"]), **config_over)
    traffic = dict(harness.load_traffic(cell["traffic"]), **mix_over)
    return harness.Context(cell=cell, config=config, traffic=traffic,
                           seed=seed, seconds=1.0, trace=False,
                           t0=time.perf_counter(), devices=jax.devices(),
                           peaks={})


def _fails(numbers: dict, limits: dict) -> bool:
    return not harness.within({k: (v, float(limits[k]))
                               for k, v in numbers.items() if k in limits})


@pytest.fixture(scope="module")
def train_readings():
    ctx = _ctx("google-train", TRAIN, TRAIN_MIX, 2**31 + 5)
    return ctx, control.train_readings(
        ctx, ["program", "control", "half", "stale"])


# An int8 cell's traffic file carries limits of its own: the stochastic
# requantization of the rows a step touches reads about 1e-4 on loss_gap
# at this size, over the fp32 cell's limit.
INT8_LIMITS = {"loss_gap": 1e-3, "change_gap": 1e-2, "exact_mismatch": 0}


@pytest.mark.parametrize("table_format", ["fp32", "int8"])
def test_train_run_is_correct_when_sound(table_format):
    mix = (dict(TRAIN_MIX, limits=INT8_LIMITS) if table_format == "int8"
           else TRAIN_MIX)
    ctx = _ctx("google-train", dict(TRAIN, table_format=table_format), mix,
               2**31 + 11)
    out = train.run(ctx)
    assert out.correct, out.checks
    assert out.failed == 0 and out.attempted > 0


def test_train_program_passes_on_a_seed_drawn_batch_stream():
    ctx = _ctx("google-train", TRAIN, TRAIN_MIX, 19)
    ctx.traffic = control.seed_stream(ctx.traffic, 19)
    r = control.train_readings(ctx, ["program"])
    assert not _fails(r["program"], ctx.traffic["limits"]), r["program"]


@pytest.mark.parametrize("variant", ["control", "half", "stale"])
def test_train_fault_and_control_fail(train_readings, variant):
    ctx, r = train_readings
    assert not _fails(r["program"], ctx.traffic["limits"]), r["program"]
    assert _fails(r[variant], ctx.traffic["limits"]), r[variant]


@pytest.mark.parametrize("fault", ["stale", "half"])
def test_train_run_with_a_fault_is_not_correct(fault):
    from repro.core import mf
    wrap = {"stale": control.stale_step, "half": control.half_batch_step}
    ctx = _ctx("google-train", TRAIN, TRAIN_MIX, 3)
    with control.patched(mf, "heat_train_step",
                         wrap[fault](mf.heat_train_step)):
        out = train.run(ctx)
    assert not out.correct
    if fault == "stale":
        assert out.checks["change_gap"][0] == pytest.approx(1.0)


@pytest.fixture(scope="module")
def serve_readings():
    ctx = _ctx("amazon-serve", SERVE, SERVE_MIX, 2**31 + 7)
    return ctx, control.serve_readings(
        ctx, ["program", "control", "altered"], 1.0)


@pytest.mark.parametrize("variant", ["control", "altered"])
def test_serve_fault_and_control_fail(serve_readings, variant):
    ctx, r = serve_readings
    assert not _fails(r["program"], ctx.traffic["limits"]), r["program"]
    assert _fails(r[variant], ctx.traffic["limits"]), r[variant]


@pytest.mark.parametrize("table_format", ["int8", "fp32"])
def test_serve_run_is_correct_when_sound_and_not_when_altered(table_format):
    from repro.core import mf
    ctx = _ctx("amazon-serve", dict(SERVE, table_format=table_format),
               SERVE_MIX, 21)
    out = serve.run(ctx)
    assert out.correct, out.checks
    assert out.failed == 0 and out.attempted == 60
    with control.patched(mf, "topk_all_items",
                         control.altered_topk(mf.topk_all_items)):
        bad = serve.run(ctx)
    assert not bad.correct, bad.checks


def test_topk_gap_reads_two_for_a_malformed_answer():
    import numpy as np
    import jax.numpy as jnp
    q = jnp.asarray(np.random.default_rng(0).integers(-127, 128, (50, 8)),
                    jnp.int8)
    _, ids = reference.topk_exact(q[:2], q, 5)
    ids = np.asarray(ids)
    assert reference.topk_gap(q[:2], q, ids, 5) == 0.0
    dup = ids.copy()
    dup[0, 1] = dup[0, 0]
    assert reference.topk_gap(q[:2], q, dup, 5) == 2.0
