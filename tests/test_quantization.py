"""Int8 embedding tables (optim/quantization.py): quantize/dequantize
edge-case properties (hypothesis) + the end-to-end contract the tentpole
promises — an int8 table trains, checkpoints, resumes bit-identically, and
serves, while every fp32-only subsystem refuses it loudly."""
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import mf
from repro.core.engine import resolve_engine
from repro.optim import quantization as qz


def _rand_table(seed: int, rows: int, cols: int, magnitude: float = 1.0):
    x = jax.random.normal(jax.random.PRNGKey(seed), (rows, cols), jnp.float32)
    return x * magnitude


def _int8_cfg(**kw):
    base = dict(num_users=40, num_items=60, emb_dim=16, num_negatives=4,
                history_len=3, table_format="int8")
    base.update(kw)
    return mf.MFConfig(**base)


def _batch(step: int, cfg: mf.MFConfig, b: int = 8) -> mf.Batch:
    r = jax.random.fold_in(jax.random.PRNGKey(99), step)
    ru, ri = jax.random.split(r)
    return mf.Batch(
        user_ids=jax.random.randint(ru, (b,), 0, cfg.num_users, jnp.int32),
        pos_ids=jax.random.randint(ri, (b,), 0, cfg.num_items, jnp.int32),
        hist_ids=jnp.zeros((b, cfg.history_len), jnp.int32),
        hist_mask=jnp.ones((b, cfg.history_len), jnp.float32))


# -- quantize/dequantize properties -----------------------------------------

@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2 ** 16), rows=st.integers(1, 24),
       cols=st.integers(1, 48), mag_exp=st.integers(-6, 6))
def test_roundtrip_error_bounded(seed, rows, cols, mag_exp):
    """Round-to-nearest: per-element error <= scale/2 (scale = absmax/127)."""
    x = _rand_table(seed, rows, cols, 10.0 ** mag_exp)
    t = qz.quantize_table(x)
    deq = np.asarray(qz.dequantize_table(t))
    bound = np.asarray(t.scale) * 0.5 + 1e-30
    assert np.all(np.abs(deq - np.asarray(x)) <= bound + 1e-6 * np.abs(deq))


@settings(max_examples=10, deadline=None)
@given(rows=st.integers(1, 16), cols=st.integers(1, 32))
def test_all_zero_rows_scale_floor(rows, cols):
    """absmax 0 must hit the scale floor, not divide by zero, and the rows
    must dequantize back to exact zeros."""
    t = qz.quantize_table(jnp.zeros((rows, cols), jnp.float32))
    assert np.all(np.asarray(t.scale) == qz.SCALE_FLOOR)
    assert np.all(np.asarray(t.q) == 0)
    assert np.all(np.asarray(qz.dequantize_table(t)) == 0.0)


def test_zero_row_table():
    """R=0 is a valid (degenerate) table for every accessor."""
    t = qz.quantize_table(jnp.zeros((0, 8), jnp.float32))
    assert t.shape == (0, 8)
    assert qz.num_rows(t) == 0
    assert qz.table_nbytes(t) == 0
    assert np.asarray(qz.dequantize_table(t)).shape == (0, 8)
    assert bool(qz.table_all_finite(t))


def test_near_overflow_absmax():
    """Rows near the fp32 max must quantize to finite scales and round-trip
    with the usual relative error, not overflow to inf."""
    big = 3.0e38
    x = jnp.array([[big, -big / 2, big / 3, 0.0]], jnp.float32)
    t = qz.quantize_table(x)
    deq = np.asarray(qz.dequantize_table(t))
    assert np.all(np.isfinite(np.asarray(t.scale)))
    assert np.all(np.isfinite(deq))
    # 0.51: fp32 rounding of scale=absmax/127 can nudge the worst element a
    # hair past the exact-arithmetic 0.5*scale bound
    assert np.all(np.abs(deq - np.asarray(x)) <= np.asarray(t.scale) * 0.51)


@settings(max_examples=10, deadline=None)
@given(frac_pct=st.integers(0, 100), base=st.integers(-5, 5))
def test_stochastic_round_unbiased(frac_pct, base):
    """E[floor(x + u)] == x: the empirical mean over many keys lands within
    a few standard errors of x, and every draw is floor(x) or ceil(x)."""
    x = jnp.full((1,), base + frac_pct / 100.0, jnp.float32)
    n = 4000
    keys = jax.vmap(jax.random.fold_in, (None, 0))(
        jax.random.PRNGKey(0), jnp.arange(n))
    draws = np.asarray(jax.vmap(lambda k: qz.stochastic_round(x, k))(keys))
    assert set(np.unique(draws)) <= {np.floor(float(x[0])),
                                     np.ceil(float(x[0])),
                                     float(x[0])}
    se = 0.5 / np.sqrt(n)
    assert abs(draws.mean() - float(x[0])) < 5 * se + 1e-6


def test_stochastic_round_exact_on_integers():
    x = jnp.arange(-3.0, 4.0, dtype=jnp.float32)
    out = np.asarray(qz.stochastic_round(x, jax.random.PRNGKey(0)))
    assert np.array_equal(out, np.asarray(x))


# -- row updates -------------------------------------------------------------

def test_apply_updates_deterministic_and_duplicate_reducing():
    """Same (table, ids, grads, rng) -> bit-identical result, and duplicate
    ids pre-reduce exactly like passing their summed gradient once."""
    t = qz.quantize_table(_rand_table(0, 12, 8))
    rng = jax.random.PRNGKey(5)
    ids = jnp.array([3, 3, 7, 3], jnp.int32)
    g = _rand_table(1, 4, 8) * 0.1
    a = qz.apply_updates(t, ids, g, 0.1, rng)
    b = qz.apply_updates(t, ids, g, 0.1, rng)
    for la, lb in zip(a, b):
        assert np.array_equal(np.asarray(la), np.asarray(lb))
    summed = jnp.stack([g[0] + g[1] + g[3], g[2]])
    c = qz.apply_updates(t, jnp.array([3, 7], jnp.int32), summed, 0.1, rng)
    deq_a = np.asarray(qz.dequantize_rows(a, jnp.array([3, 7])))
    deq_c = np.asarray(qz.dequantize_rows(c, jnp.array([3, 7])))
    np.testing.assert_allclose(deq_a, deq_c, atol=2e-2)
    # untouched rows are bit-identical to the original
    rest = jnp.array([0, 1, 2, 4, 5, 6, 8, 9, 10, 11])
    assert np.array_equal(np.asarray(a.q[rest]), np.asarray(t.q[rest]))


def test_error_feedback_preserves_small_updates():
    """Per-step |lr*g| far below the quantization step must still accumulate:
    the residual feeds back, so N tiny updates move the row by ~N*lr*g
    instead of being rounded away."""
    row = jnp.ones((1, 16), jnp.float32)
    t = qz.quantize_table(row)
    g = jnp.full((1, 16), 1.0, jnp.float32)
    lr, n = 1e-3, 200                     # step ~0.001 << scale ~0.008
    for i in range(n):
        t = qz.apply_updates(t, jnp.array([0], jnp.int32), g, lr,
                             jax.random.fold_in(jax.random.PRNGKey(0), i))
    moved = float(np.mean(np.asarray(qz.dequantize_rows(t, jnp.array([0])))))
    assert abs((1.0 - moved) - n * lr) < 0.25 * n * lr


def test_apply_updates_many_matches_concat():
    t = qz.quantize_table(_rand_table(0, 10, 8))
    rng = jax.random.PRNGKey(9)
    g1 = (jnp.array([1, 2], jnp.int32), _rand_table(1, 2, 8))
    g2 = (jnp.array([2, 5], jnp.int32), _rand_table(2, 2, 8))
    a = qz.apply_updates_many(t, [g1, g2], 0.1, rng)
    b = qz.apply_updates(t, jnp.concatenate([g1[0], g2[0]]),
                         jnp.concatenate([g1[1], g2[1]]), 0.1, rng)
    for la, lb in zip(a, b):
        assert np.array_equal(np.asarray(la), np.asarray(lb))


# -- layout polymorphism -----------------------------------------------------

def test_gather_rows_kernel_parity():
    """The Pallas gather-dequant kernel (interpret mode on CPU) must be
    bit-identical to the plain fused gather-multiply."""
    t = qz.quantize_table(_rand_table(0, 32, 16))
    ids = jnp.array([0, 31, 7, 7, 12], jnp.int32)
    plain = np.asarray(qz.gather_rows(t, ids))
    kernel = np.asarray(qz.gather_rows(t, ids, use_kernel=True))
    assert np.array_equal(plain, kernel)


def test_accessors_match_fp32_semantics():
    x = _rand_table(3, 20, 8)
    t = qz.quantize_table(x)
    assert qz.num_rows(t) == qz.num_rows(x) == 20
    assert qz.logical_dtype(t) == jnp.float32
    assert np.asarray(qz.slice_rows(t, 4, 9)).shape == (5, 8)
    assert np.array_equal(np.asarray(qz.slice_rows(t, 2, 8)),
                          np.asarray(qz.dequantize_rows(t, jnp.arange(2, 8))))


def test_table_bytes_halved():
    """The acceptance gate: int8 serving bytes <= half of fp32 (K=64 gives
    ~0.27x), and the training carry (incl. residual) stays under fp32 too."""
    x = _rand_table(0, 256, 64)
    t = qz.quantize_table(x)
    fp32_bytes = qz.table_nbytes(x)
    assert qz.table_nbytes(t) <= 0.5 * fp32_bytes
    assert qz.carry_nbytes(t) < fp32_bytes
    assert qz.carry_nbytes(t) > qz.table_nbytes(t)


def test_table_spec_distinguishes_layouts():
    x = _rand_table(0, 8, 4)
    assert qz.table_spec((x, x)) != qz.table_spec((qz.quantize_table(x), x))
    assert qz.table_spec((x,)) != qz.table_spec((x[:4],))


# -- end-to-end: train / checkpoint / resume / serve -------------------------

def test_init_mf_validates_table_format():
    with pytest.raises(ValueError, match="table_format"):
        mf.init_mf(jax.random.PRNGKey(0), _int8_cfg(table_format="int4"))
    with pytest.raises(ValueError, match="table_format"):
        resolve_engine(_int8_cfg(table_format="fp16"))


@pytest.mark.parametrize("backend,sampler", [
    ("fused", "uniform"), ("pallas", "uniform"), ("autodiff", "tile"),
    ("fused", "in_batch")])
def test_int8_train_step_runs(backend, sampler):
    cfg = _int8_cfg(backend=backend, sampler=sampler, tile_size=16)
    eng = resolve_engine(cfg)
    state = mf.init_mf(jax.random.PRNGKey(0), cfg)
    assert isinstance(state.params.user_table, qz.QuantizedTable)
    for step in range(3):
        r = jax.random.fold_in(jax.random.PRNGKey(7), step)
        state, loss = mf.heat_train_step(state, _batch(step, cfg), r, cfg,
                                         engine=eng)
    assert np.isfinite(float(loss))
    assert state.params.user_table.q.dtype == jnp.int8


def test_int8_restart_bit_identical():
    """Crash at a mid-window step, resume from the checkpoint, and land on
    the exact same int8 bits as the uninterrupted run — stochastic rounding
    included, because the rounding keys are (seed, step)-pure."""
    from repro.data import pipeline
    from repro.train import trainer
    cfg = _int8_cfg()
    ds = pipeline.synth_cf_dataset(cfg.num_users, cfg.num_items, seed=0)
    quiet = lambda *_: None
    s1, _ = trainer.train_mf(cfg, ds, 24, batch_size=16, seed=3, log=quiet)
    with tempfile.TemporaryDirectory() as d:
        # train_mf self-heals: the injected crash restores from the step-8
        # checkpoint and replays 8..24 with the same (seed, step) keys
        s2, _ = trainer.train_mf(cfg, ds, 24, batch_size=16, seed=3,
                                 ckpt_dir=d, ckpt_every=8, fail_at_step=13,
                                 log=quiet)
    for la, lb in zip(jax.tree_util.tree_leaves(s1.params),
                      jax.tree_util.tree_leaves(s2.params)):
        assert np.array_equal(np.asarray(la), np.asarray(lb))


def test_int8_checkpoint_roundtrip_bit_exact():
    from repro.train import checkpoint as ckpt
    cfg = _int8_cfg()
    state = mf.init_mf(jax.random.PRNGKey(0), cfg)
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(d, 3, state)
        tgt = mf.init_mf(jax.random.PRNGKey(1), cfg)
        restored = ckpt.restore(d, tgt, 3)
        r = restored[0] if isinstance(restored, tuple) else restored
    assert r.params.user_table.q.dtype == jnp.int8
    for la, lb in zip(jax.tree_util.tree_leaves(state.params),
                      jax.tree_util.tree_leaves(r.params)):
        assert np.array_equal(np.asarray(la), np.asarray(lb))


def test_int8_serving_and_refresh_guard():
    """An int8 state serves through BatchingRecommender; a refresh with an
    fp32-layout state is refused (degraded, previous snapshot stays live)."""
    from repro.launch.server import BatchingRecommender
    cfg = _int8_cfg()
    state = mf.init_mf(jax.random.PRNGKey(0), cfg)
    with BatchingRecommender(state, 5, max_batch=4, warmup=True) as rec:
        out = rec.recommend_many([0, 1, 2])
        assert out.shape == (3, 5)
        assert rec.trace_count == 1
        assert rec.refresh_from(state)
        fp32_state = mf.init_mf(jax.random.PRNGKey(0),
                                _int8_cfg(table_format="fp32"))
        assert not rec.refresh_from(fp32_state)
        assert rec.health["status"] == "degraded"
        with pytest.raises(ValueError, match="refusing the swap"):
            rec.refresh_from(fp32_state, on_error="raise")
        assert rec.trace_count == 1     # nothing retraced through all that


def test_int8_retrieval_index_and_pruned_topk():
    from repro.core import retrieval as rtv
    cfg = _int8_cfg()
    state = mf.init_mf(jax.random.PRNGKey(0), cfg)
    idx = rtv.build_retrieval_index(state.params.item_table, tile_rows=16,
                                    seed=0)
    out = np.asarray(rtv.topk_pruned(state.params,
                                     jnp.array([0, 1], jnp.int32), 5, idx,
                                     expand_tiles=2))
    assert out.shape == (2, 5)
    exact = np.asarray(mf.topk_all_items(state.params,
                                         jnp.array([0, 1], jnp.int32), 5,
                                         item_chunk=16))
    assert exact.shape == (2, 5)


def test_guard_stats_on_quantized_tables():
    from repro.resilience.guard import DivergenceGuard, GuardConfig
    cfg = _int8_cfg()
    state = mf.init_mf(jax.random.PRNGKey(0), cfg)
    g = DivergenceGuard(GuardConfig())
    assert g.check(state.params, jnp.ones((4,), jnp.float32)) is None
    bad = state.params._replace(item_table=state.params.item_table._replace(
        scale=state.params.item_table.scale.at[0, 0].set(jnp.nan)))
    assert g.check(bad, jnp.ones((4,), jnp.float32)) is not None


def test_fp32_only_subsystems_refuse_int8():
    from repro.core import mf_distributed as md
    from repro.stream.service import StreamingTrainer
    from repro.stream.sources import SyntheticStream
    cfg = _int8_cfg()
    with pytest.raises(NotImplementedError, match="fp32"):
        md.state_specs(cfg, mesh=None)
    with pytest.raises(NotImplementedError, match="fp32"):
        StreamingTrainer(cfg, SyntheticStream(cfg.num_users, cfg.num_items))
