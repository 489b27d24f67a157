"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the semantic ground truth its kernel is tested against
(tests/test_kernels.py sweeps shapes/dtypes and asserts allclose).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.losses import ccl_loss_autodiff


def ccl_stats_ref(user, pos, negs):
    """Oracle for ccl_similarity.ccl_stats_pallas (float32 accumulation)."""
    u = user.astype(jnp.float32)
    p = pos.astype(jnp.float32)
    n = negs.astype(jnp.float32)
    uu = jnp.sum(u * u, axis=-1, keepdims=True)
    pp = jnp.sum(p * p, axis=-1, keepdims=True)
    up = jnp.sum(u * p, axis=-1, keepdims=True)
    nn = jnp.sum(n * n, axis=-1)
    un = jnp.einsum("bk,bnk->bn", u, n)
    return uu, pp, up, nn, un


def ccl_loss_ref(user, pos, negs, mu=1.0, theta=0.0):
    """Oracle for the full fused loss: plain autodiff over the reference math."""
    return ccl_loss_autodiff(user.astype(jnp.float32), pos.astype(jnp.float32),
                             negs.astype(jnp.float32), mu, theta, "cosine")


def ccl_grads_ref(user, pos, negs, mu=1.0, theta=0.0):
    """Oracle gradients for the backward kernel (jax.grad of the reference)."""
    g = jax.grad(ccl_loss_ref, argnums=(0, 1, 2))(user, pos, negs, mu, theta)
    return tuple(x.astype(t.dtype) for x, t in zip(g, (user, pos, negs)))


def rows_update_ref(table, ids, grads, lr):
    """Oracle for embedding_update: sparse SGD row scatter (duplicates add)."""
    return table.at[ids].add((-lr * grads).astype(table.dtype))


def attention_ref(q, k, v, *, causal=True, scale=None):
    """Oracle for flash_attention: full-materialization softmax attention.

    q: (B, Hq, S, D), k/v: (B, Hkv, S, D) with Hq a multiple of Hkv (GQA).
    """
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    scale = scale if scale is not None else 1.0 / jnp.sqrt(d).astype(jnp.float32)
    kr = jnp.repeat(k, group, axis=1)
    vr = jnp.repeat(v, group, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        kr.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.tril(jnp.ones((s, s), bool))
        logits = jnp.where(mask[None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vr.astype(jnp.float32))
    return out.astype(q.dtype)


def topk_scan_ref(u, items, scale, k, *, similarity, item_chunk,
                  exclude_mask=None):
    """Oracle for topk_scan: a ``lax.fori_loop`` over (B, item_chunk) score
    blocks of the zero-padded table, each concatenated after the running
    (B, k) top-k and merged by ``lax.top_k`` (ties to the lower position:
    the running entries, then the block's in id order).

    u: (B, K) f32; items: (I, K) int8 with ``scale`` (I, 1), dequantized
    per block, or a float table with ``scale`` None; ``k`` <= I.
    """
    num_items = items.shape[0]
    c = item_chunk
    num_chunks = -(-num_items // c)
    pad = num_chunks * c - num_items
    items_p = jnp.pad(items, ((0, pad), (0, 0)))
    scale_p = (None if scale is None
               else jnp.pad(scale, ((0, pad), (0, 0)), constant_values=1.0))
    mask_p = (None if exclude_mask is None
              else jnp.pad(exclude_mask, ((0, 0), (0, pad)),
                           constant_values=True))
    b = u.shape[0]

    def body(i, carry):
        best_s, best_i = carry
        s0 = i * c
        block = jax.lax.dynamic_slice_in_dim(items_p, s0, c).astype(
            jnp.float32)
        if scale_p is not None:
            block = block * jax.lax.dynamic_slice_in_dim(scale_p, s0, c)
        sc = u @ block.T
        if similarity == "cosine":
            un = jnp.linalg.norm(u, axis=-1, keepdims=True).clip(1e-12)
            bn = jnp.linalg.norm(block, axis=-1).clip(1e-12)
            sc = sc / un / bn[None, :]
        ids = s0 + jnp.arange(c, dtype=jnp.int32)
        dead = ids[None, :] >= num_items
        if mask_p is not None:
            dead = dead | jax.lax.dynamic_slice_in_dim(mask_p, s0, c, axis=1)
        sc = jnp.where(dead, -jnp.inf, sc.astype(best_s.dtype))
        cat_s = jnp.concatenate([best_s, sc], axis=1)
        cat_i = jnp.concatenate(
            [best_i, jnp.broadcast_to(ids[None, :], sc.shape)], axis=1)
        best_s, idx = jax.lax.top_k(cat_s, k)
        return best_s, jnp.take_along_axis(cat_i, idx, axis=1)

    _, best_i = jax.lax.fori_loop(
        0, num_chunks, body,
        (jnp.full((b, k), -jnp.inf, u.dtype), jnp.zeros((b, k), jnp.int32)))
    return best_i
