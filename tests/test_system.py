"""End-to-end system behaviour: the paper's full pipeline (data -> HEAT train
-> evaluate -> serve) and the LM pipeline (train -> prefill -> decode)."""
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.metrics import evaluate_ranking, topk_exclude_train
from repro.core.mf import MFConfig, scores_all_items
from repro.data import pipeline
from repro.models import lm
from repro.train import trainer


def test_end_to_end_cf_recommendation():
    """Synthetic dataset -> HEAT training (tiling + aggregation + fused CCL)
    -> Recall@20 beats random -> top-k serving excludes training items."""
    ds = pipeline.synth_cf_dataset(128, 256, interactions_per_user=12,
                                   num_clusters=8, seed=1)
    cfg = MFConfig(num_users=128, num_items=256, emb_dim=16, num_negatives=16,
                   lr=0.1, history_len=4, flush_every=8,
                   tile_size=64, refresh_interval=64)
    state, losses = trainer.train_mf(cfg, ds, steps=250, batch_size=64,
                                     log=lambda *_: None)
    assert losses[-1] < losses[0]

    users = jnp.arange(cfg.num_users)
    scores = scores_all_items(state.params, users)
    train_mask = jnp.asarray(ds.train_mask())
    metrics = evaluate_ranking(scores, train_mask, jnp.asarray(ds.test_mask()))
    assert float(metrics["recall@20"]) > (20 / 256) * 1.5

    # serving: top-k never recommends a training positive
    topk = topk_exclude_train(scores, train_mask, 10)
    tm = np.asarray(train_mask)
    for u in range(0, 128, 17):
        assert not tm[u, np.asarray(topk[u])].any()


def test_end_to_end_lm_train_then_serve():
    """Reduced LM: a few train steps, then prefill + 4 decode steps produce
    finite, shape-correct logits (the serving path end-to-end)."""
    cfg = get_config("smollm-360m").reduced()
    opts = lm.TrainOptions(loss="heat", remat="none", attn_chunk=8)
    tcfg = trainer.TrainerConfig(steps=5, lr=1e-2, batch_size=4, seq_len=16,
                                 log_every=0)
    state, losses = trainer.train_lm(cfg, opts, tcfg, log=lambda *_: None)
    assert np.isfinite(losses).all()

    prompt = {"tokens": jnp.ones((2, 8), jnp.int32)}
    logits, cache = lm.prefill(state.params, prompt, cfg, opts)
    cache = lm.pad_cache(cache, cfg, 8 + 4)
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    for i in range(4):
        logits_t, cache = lm.decode_step(state.params, cache, tok,
                                         jnp.asarray(8 + i, jnp.int32), cfg, opts)
        tok = jnp.argmax(logits_t[:, 0], -1)[:, None].astype(jnp.int32)
        assert logits_t.shape == (2, 1, cfg.vocab)
        assert np.isfinite(np.asarray(logits_t)).all()


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/cache"])
def test_entry_point_compile_cache_placement(env_dir, monkeypatch):
    """Entry points keep the compile cache where JAX_COMPILATION_CACHE_DIR
    says (the helper then sets nothing), else at <checkout>/.jax_cache."""
    from repro.launch import COMPILE_CACHE_DIR, enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    try:
        enable_compile_cache()
        got = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    repo = Path(__file__).resolve().parents[1]
    assert COMPILE_CACHE_DIR == repo / ".jax_cache"
    assert got == (before if env_dir else str(repo / ".jax_cache"))
