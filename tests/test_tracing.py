"""The program's trace points (``repro.analysis.tracing``): every device
scope reaches the compiled HLO's ``op_name`` metadata of the HEAT window and
the top-k call, and every host span reaches a profiler trace."""
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import tracing
from repro.core import mf
from repro.data import pipeline
from repro.launch.server import BatchingRecommender
from repro.train import trainer

USERS, ITEMS = 40, 60


def _scopes(hlo_text: str) -> set:
    """Scope names found in the op_name metadata of compiled HLO text."""
    names = re.findall(r'op_name="([^"]*)"', hlo_text)
    return {s for n in names for s in re.findall(r"(?:heat|topk)\.[a-z_]+", n)}


def _executor(cfg):
    ds = pipeline.synth_cf_dataset(USERS, ITEMS, interactions_per_user=8)
    dds = pipeline.device_cf_dataset(ds)
    body = mf.make_scan_body(
        cfg, lambda s: pipeline.cf_batch_device(dds, 0, s, 8,
                                                cfg.history_len), 0)
    return trainer.EpochExecutor(body, 4)


def _cfg(**kw):
    return mf.MFConfig(num_users=USERS, num_items=ITEMS, emb_dim=8,
                       num_negatives=4, lr=0.05, tile_size=16,
                       refresh_interval=3, **kw)


@pytest.mark.parametrize("kw", [
    {},
    {"table_format": "int8"},
    {"backend": "pallas", "update_impl": "pallas"},
    {"history_len": 3},
], ids=["fused-fp32", "fused-int8", "pallas-fp32", "aggregation"])
def test_every_heat_scope_is_in_the_compiled_window(kw):
    cfg = _cfg(**kw)
    ex = _executor(cfg)
    state = mf.init_mf(jax.random.PRNGKey(0), cfg)
    hlo = ex._compiled(4).lower(state, jnp.int32(0)).compile().as_text()
    assert set(tracing.HEAT_SCOPES) <= _scopes(hlo)


@pytest.mark.parametrize("item_chunk", [16, None], ids=["chunked", "whole"])
def test_every_topk_scope_is_in_the_compiled_call(item_chunk):
    state = mf.init_mf(jax.random.PRNGKey(0), _cfg(table_format="int8"))
    fn = jax.jit(lambda p, u: mf.topk_all_items(p, u, 5,
                                                item_chunk=item_chunk))
    hlo = fn.lower(state.params, jnp.arange(4, dtype=jnp.int32)) \
        .compile().as_text()
    assert set(tracing.TOPK_SCOPES) <= _scopes(hlo)


def test_scope_and_span_names_are_distinct_and_flat():
    names = tracing.HEAT_SCOPES + tracing.TOPK_SCOPES + tracing.HOST_SPANS
    assert len(set(names)) == len(names)
    assert all(re.fullmatch(r"(heat|topk|train|serve)\.[a-z_]+", n)
               for n in names)


def test_every_host_span_reaches_the_profiler_trace(tmp_path):
    """One training window through ``run_window`` and one queued request,
    traced: the host plane holds each of the program's spans."""
    from jax.profiler import ProfileData
    cfg = _cfg()
    ex = _executor(cfg)
    state = mf.init_mf(jax.random.PRNGKey(0), cfg)
    state, _, _ = trainer.run_window(ex, state, 0, 4)      # compile first
    with BatchingRecommender(mf.init_mf(jax.random.PRNGKey(1), _cfg()), 5,
                             max_batch=4, max_wait_ms=1.0) as server:
        jax.profiler.start_trace(str(tmp_path))
        try:
            state, losses, n = trainer.run_window(ex, state, 4, 8)
            answer = server.recommend(3)
        finally:
            jax.profiler.stop_trace()
    assert n == 4 and np.all(np.isfinite(losses)) and answer.shape == (5,)
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    profile = ProfileData.from_file(path)
    names = {ev.name for plane in profile.planes
             if not plane.name.startswith("/device:")
             for line in plane.lines for ev in line.events}
    assert set(tracing.HOST_SPANS) <= names
