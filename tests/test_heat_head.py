"""HEAT sampled-CCL LM head (repro.core.heat_head) — the paper's technique as
an LM feature, now resolved from the unified engine registries: gradient
flow, tile schedule, masking, softmax-baseline parity, and backend parity on
the step-shared negative layout.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import samplers
from repro.core.heat_head import (
    HeatHeadConfig,
    full_softmax_loss,
    sampled_ccl_loss,
)


def _data(b=2, s=8, d=16, v=64, seed=0):
    r = jax.random.PRNGKey(seed)
    h = jax.random.normal(r, (b, s, d))
    t = jax.random.randint(jax.random.fold_in(r, 1), (b, s), 0, v)
    table = jax.random.normal(jax.random.fold_in(r, 2), (v, d)) * 0.1
    return h, t, table


def test_gradients_reach_table_and_hidden():
    """Positive + negative rows of the table receive gradients (no detached
    copies — DESIGN.md §4); hidden states too."""
    h, t, table = _data()
    cfg = HeatHeadConfig(num_negatives=8)

    def loss(hh, tab):
        l, _ = sampled_ccl_loss(hh, t, tab, jax.random.PRNGKey(3), cfg)
        return l

    gh, gt = jax.grad(loss, argnums=(0, 1))(h, table)
    assert float(jnp.abs(gh).max()) > 0
    assert float(jnp.abs(gt).max()) > 0
    # rows never touched (neither positive nor sampled negative) get zero grad
    touched_rows = int((jnp.abs(gt).sum(axis=1) > 0).sum())
    assert touched_rows <= t.size + cfg.num_negatives


def test_no_private_loss_or_tile_in_heat_head():
    """Acceptance (ISSUE 3): heat_head carries no loss math or tile type of
    its own — it resolves everything from core.engine's registries and
    core.samplers' TileState."""
    import inspect

    from repro.core import heat_head
    src = inspect.getsource(heat_head)
    assert "HeadTileState" not in src
    assert "resolve_engine" in src
    assert not hasattr(heat_head, "head_tile_init")
    assert not hasattr(heat_head, "head_tile_refresh")


@pytest.mark.parametrize("backend", ["fused", "autodiff", "pallas"])
def test_head_backend_parity(backend):
    """Every loss backend produces the same head loss and table gradient for
    the same rng (the draw is engine-independent) — the Pallas fused CCL
    kernels are reachable from LM training."""
    h, t, table = _data()
    rng = jax.random.PRNGKey(7)
    mask = jnp.ones(t.shape).at[:, -2:].set(0)

    def run(name):
        cfg = HeatHeadConfig(num_negatives=8, backend=name)

        def loss(tab):
            l, _ = sampled_ccl_loss(h, t, tab, rng, cfg, mask=mask)
            return l

        return jax.value_and_grad(loss)(table)

    l_ref, g_ref = run("autodiff")
    l_got, g_got = run(backend)
    np.testing.assert_allclose(float(l_ref), float(l_got), atol=1e-5)
    np.testing.assert_allclose(np.asarray(g_ref), np.asarray(g_got),
                               atol=1e-5)


def test_loss_decreases_under_sgd():
    h, t, table = _data()
    cfg = HeatHeadConfig(num_negatives=8, tile_size=32, refresh_interval=4)
    tile = samplers.id_tile_init(jax.random.PRNGKey(9), table.shape[0],
                                 cfg.tile_size)

    def loss(tab, tl, rng):
        return sampled_ccl_loss(h, t, tab, rng, cfg, tl)

    losses = []
    for i in range(25):
        rng = jax.random.PRNGKey(100 + i)
        (l, tile), g = jax.value_and_grad(loss, has_aux=True)(table, tile, rng)
        table = table - 0.5 * g
        losses.append(float(l))
    assert losses[-1] < losses[0]
    assert np.isfinite(losses).all()


@settings(deadline=None, max_examples=10)
@given(interval=st.integers(2, 8), steps=st.integers(1, 20))
def test_head_tile_schedule(interval, steps):
    """The id-only vocab tile follows the §4.2 refresh schedule through the
    shared samplers.tile_refresh (tile_emb stays None throughout)."""
    table = jnp.zeros((100, 4))
    tile = samplers.id_tile_init(jax.random.PRNGKey(0), 100, 16)
    for i in range(steps):
        tile = samplers.tile_refresh(
            tile, jax.random.fold_in(jax.random.PRNGKey(1), i), table,
            interval)
    assert int(tile.step) == steps % interval
    assert np.asarray(tile.tile_ids).max() < 100
    assert tile.tile_emb is None


def test_mask_excludes_padding():
    h, t, table = _data()
    cfg = HeatHeadConfig(num_negatives=4)
    mask = jnp.ones_like(t).at[:, -3:].set(0)
    rng = jax.random.PRNGKey(5)
    l_masked, _ = sampled_ccl_loss(h, t, table, rng, cfg, mask=mask)
    # corrupting masked positions must not change the loss
    h2 = h.at[:, -3:].set(99.0)
    l_masked2, _ = sampled_ccl_loss(h2, t, table, rng, cfg, mask=mask)
    np.testing.assert_allclose(l_masked, l_masked2, atol=1e-5)


def test_softmax_baseline_sanity():
    """Full-softmax head: CE of a uniform model ~ log(V); mask honored."""
    h = jnp.zeros((2, 4, 8))
    t = jnp.zeros((2, 4), jnp.int32)
    table = jnp.zeros((32, 8))
    np.testing.assert_allclose(full_softmax_loss(h, t, table), np.log(32),
                               rtol=1e-5)


def test_heat_head_cheaper_than_softmax_in_flops():
    """Structural claim of DESIGN.md §4: the sampled head's matmul is
    (T,d)x(d,1+n) vs (T,d)x(d,V) — compare compiled FLOP counts."""
    h, t, table = _data(b=4, s=32, v=4096)
    cfg = HeatHeadConfig(num_negatives=8)
    heat = jax.jit(lambda hh, tab: sampled_ccl_loss(
        hh, t, tab, jax.random.PRNGKey(0), cfg)[0]).lower(h, table).compile()
    soft = jax.jit(lambda hh, tab: full_softmax_loss(
        hh, t, tab)).lower(h, table).compile()
    f_heat = heat.cost_analysis().get("flops", 0.0)
    f_soft = soft.cost_analysis().get("flops", 0.0)
    assert f_heat < f_soft / 10, (f_heat, f_soft)
