"""Jit-ready public wrappers around the Pallas kernels.

Dispatch policy: on a TPU backend the kernels run compiled; on the CPU
backend (tests, laptops) they run in ``interpret=True`` mode, which executes
the kernel body on XLA-CPU for correctness validation.  Any other backend is
an error, never a silent fall back to the interpreter.
``use_kernel=False`` falls back to the pure-jnp reference path (used both as
the oracle and as the XLA-fusion baseline in benchmarks).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.ccl_similarity import (
    ccl_bwd_pallas,
    ccl_bwd_shared_pallas,
    ccl_stats_pallas,
    ccl_stats_shared_pallas,
    per_example_block_b,
)
from repro.kernels.embedding_update import (
    gather_fma_rows,
    launch_count,
    reset_launch_count,
)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.topk_scan import topk_scan_pallas

EPS = 1e-12


def default_interpret() -> bool:
    """True on the CPU backend (Pallas interpreted), False on a TPU (Pallas
    compiled); any other backend raises."""
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(
            f"Pallas kernels run compiled on 'tpu' or interpreted on 'cpu'; "
            f"the default backend is {backend!r}")
    return backend == "cpu"


def _pad_rows(x: jax.Array, target: int) -> jax.Array:
    pad = target - x.shape[0]
    if pad == 0:
        return x
    return jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))


# ----------------------------------------------------------------------------
# Fused CCL loss: stats kernel forward + analytic Eq.4/5 backward kernel.
# ----------------------------------------------------------------------------

def _row_tile(block_b, user, negs):
    """Batch rows per kernel tile: ``block_b`` or the VMEM-sized default."""
    if block_b is None:
        block_b = per_example_block_b(negs.shape[1], user.shape[1])
    return min(block_b, user.shape[0])


def _ccl_fwd(user, pos, negs, mu, theta, block_b, interpret):
    b = user.shape[0]
    bb = _row_tile(block_b, user, negs)
    bp = ((b + bb - 1) // bb) * bb
    u_p, p_p, n_p = _pad_rows(user, bp), _pad_rows(pos, bp), _pad_rows(negs, bp)
    uu, pp, up, nn, un = ccl_stats_pallas(u_p, p_p, n_p, block_b=bb,
                                          interpret=interpret)
    inv_u = jax.lax.rsqrt(uu[:b] + EPS)
    pos_sim = (up[:b] * inv_u * jax.lax.rsqrt(pp[:b] + EPS))[:, 0]
    neg_sim = un[:b] * inv_u * jax.lax.rsqrt(nn[:b] + EPS)
    neg_part = jnp.maximum(neg_sim - theta, 0.0)
    loss = jnp.mean((1.0 - pos_sim)
                    + (mu / negs.shape[1]) * jnp.sum(neg_part, axis=-1))
    return loss.astype(user.dtype), (u_p, p_p, n_p, uu, pp, up, nn, un)


def make_ccl_loss_pallas(mu: float = 1.0, theta: float = 0.0,
                         block_b: int | None = None,
                         interpret: bool | None = None):
    """Factory returning a fused-CCL loss fn with kernel fwd+bwd.

    ``fn(user, pos, negs) -> scalar``; gradients flow to all three inputs via
    the analytic backward kernel (residual reuse, §4.4).  ``block_b=None``
    sizes the batch tile from n and K (``per_example_block_b``).
    """
    interp = default_interpret() if interpret is None else interpret

    @jax.custom_vjp
    def fn(user, pos, negs):
        loss, _ = _ccl_fwd(user, pos, negs, mu, theta, block_b, interp)
        return loss

    def fwd(user, pos, negs):
        loss, res = _ccl_fwd(user, pos, negs, mu, theta, block_b, interp)
        return loss, (res, user.shape[0])

    def bwd(saved, g):
        (u_p, p_p, n_p, uu, pp, up, nn, un), b = saved
        bb = _row_tile(block_b, u_p, n_p)
        g_row = (g / b).astype(jnp.float32)
        du, dp, dn = ccl_bwd_pallas(u_p, p_p, n_p, uu, pp, up, nn, un, g_row,
                                    mu=mu, theta=theta, block_b=bb,
                                    interpret=interp)
        return du[:b], dp[:b], dn[:b]

    fn.defvjp(fwd, bwd)
    return fn


def _ccl_shared_fwd(user, pos, negs, w, mu, theta, block_b, interpret):
    t = user.shape[0]
    n = negs.shape[0]
    bt = min(block_b, t)
    tp = ((t + bt - 1) // bt) * bt
    u_p, p_p = _pad_rows(user, tp), _pad_rows(pos, tp)
    w_p = _pad_rows(w.reshape(t, 1).astype(jnp.float32), tp)  # pads carry w=0
    uu, pp, up, nn, un = ccl_stats_shared_pallas(u_p, p_p, negs, block_b=bt,
                                                 interpret=interpret)
    inv_u = jax.lax.rsqrt(uu[:t] + EPS)
    pos_sim = (up[:t] * inv_u * jax.lax.rsqrt(pp[:t] + EPS))[:, 0]
    neg_sim = un[:t] * inv_u * jax.lax.rsqrt(nn + EPS)        # (T, n)
    rows = ((1.0 - pos_sim)
            + (mu / n) * jnp.sum(jnp.maximum(neg_sim - theta, 0.0), axis=-1))
    loss = jnp.sum(rows * w.reshape(t))
    return loss.astype(user.dtype), (u_p, p_p, uu, pp, up, nn, un, w_p, rows)


def make_ccl_loss_shared_pallas(mu: float = 1.0, theta: float = 0.0,
                                block_b: int = 256,
                                interpret: bool | None = None):
    """Factory for the *step-shared* negative layout (LM HEAT head).

    ``fn(user (T,K), pos (T,K), negs (n,K), w (T,)) -> scalar`` — the weighted
    CCL of ``core.losses.ccl_loss_fused_w``, with the stats forward and the
    analytic Eq. 4/5 backward running as Pallas kernels.  ``w`` must already
    be normalized (``core.losses.loss_weights``); masked rows (w=0) are
    exactly dropped from loss and gradients, which is also what makes the
    padded tile rows inert.
    """
    interp = default_interpret() if interpret is None else interpret

    @jax.custom_vjp
    def fn(user, pos, negs, w):
        loss, _ = _ccl_shared_fwd(user, pos, negs, w, mu, theta, block_b,
                                  interp)
        return loss

    def fwd(user, pos, negs, w):
        loss, res = _ccl_shared_fwd(user, pos, negs, w, mu, theta, block_b,
                                    interp)
        return loss, (res, negs, user.shape[0])

    def bwd(saved, g):
        (u_p, p_p, uu, pp, up, nn, un, w_p, rows), negs, t = saved
        bt = min(block_b, u_p.shape[0])
        du, dp, dn = ccl_bwd_shared_pallas(
            u_p, p_p, negs, uu, pp, up, nn, un, w_p,
            jnp.asarray(g, jnp.float32), mu=mu, theta=theta, block_b=bt,
            interpret=interp)
        return du[:t], dp[:t], dn.astype(negs.dtype), (g * rows).astype(u_p.dtype)

    fn.defvjp(fwd, bwd)
    return fn


# ----------------------------------------------------------------------------
# Sparse embedding row update (§3.1/§4.5): pre-reduce -> gather+fma -> scatter.
# ----------------------------------------------------------------------------

def sparse_row_update(table: jax.Array, ids: jax.Array, grads: jax.Array, lr,
                      *, use_kernel: bool = True,
                      interpret: bool | None = None) -> jax.Array:
    """table.at[ids].add(-lr*grads), HEAT-style.

    ids (B,) may contain duplicates; they are pre-reduced with a sorted
    segment-sum (deterministic conflict alleviation) so the kernel's output
    rows scatter conflict-free.
    """
    ids = ids.reshape(-1)
    grads = grads.reshape(-1, grads.shape[-1])
    if not use_kernel:
        return ref.rows_update_ref(table, ids, grads, lr)
    interp = default_interpret() if interpret is None else interpret

    b = ids.shape[0]
    order = jnp.argsort(ids)
    sids = ids[order]
    sg = grads[order]
    first = jnp.concatenate([jnp.ones((1,), bool), sids[1:] != sids[:-1]])
    seg = jnp.cumsum(first) - 1                       # segment index per row
    reduced = jnp.zeros_like(sg).at[seg].add(sg)      # summed grads, rows 0..u-1
    uids = jnp.zeros_like(sids).at[seg].max(sids)     # unique ids, rows 0..u-1
    num_unique = seg[-1] + 1

    new_rows = gather_fma_rows(table, uids, reduced, lr, interpret=interp)
    # Scatter only the live rows; padding lanes are dropped out-of-bounds.
    scatter_ids = jnp.where(jnp.arange(b) < num_unique, uids, table.shape[0])
    return table.at[scatter_ids].set(new_rows, mode="drop")


def fused_rows_update(table: jax.Array, groups, lr, *, use_kernel: bool = True,
                      interpret: bool | None = None) -> jax.Array:
    """Single-launch row update for one step's worth of gradient groups.

    ``groups`` is a list of ``(ids, grads)`` pairs addressing the same table
    (HEAT's pos/neg/history item gradients).  Instead of one pre-reduce +
    kernel launch per group (the chained path this replaces), the groups are
    concatenated and the whole step runs ONE duplicate-id segment-sum and ONE
    gather-FMA launch — ids shared *across* groups are pre-reduced together,
    which both preserves scatter-add semantics exactly and cuts kernel
    launches per step by the number of groups (3x for pos/neg/history).
    """
    # Concat inlined (rather than core.tiling.concat_groups) to keep the
    # kernels layer free of core imports.
    ids = jnp.concatenate([i.reshape(-1) for i, _ in groups])
    grads = jnp.concatenate([g.reshape(-1, g.shape[-1]) for _, g in groups])
    return sparse_row_update(table, ids, grads, lr, use_kernel=use_kernel,
                             interpret=interpret)


# ----------------------------------------------------------------------------
# Exact full-catalog top-k: one kernel streams the item table.
# ----------------------------------------------------------------------------

def topk_scan(u: jax.Array, items: jax.Array, scale, k: int, *,
              similarity: str, item_chunk: int, exclude_mask=None,
              interpret: bool | None = None):
    """Top-k item ids of the (B, K) user rows ``u`` over an int8 payload
    ``items`` with its (I, 1) ``scale``, or a float table with ``scale``
    None, scored in chunks of ``topk_scan.chunk_width(item_chunk)`` items.
    Returns the (B, k) ids and the number of chunks merged into the
    running top-k (kernels/topk_scan.py); ``ref.topk_scan_ref`` is the
    oracle."""
    interp = default_interpret() if interpret is None else interpret
    return topk_scan_pallas(u, items, scale, exclude_mask, k=k,
                            similarity=similarity, item_chunk=item_chunk,
                            interpret=interp)


# ----------------------------------------------------------------------------
# Attention dispatcher.
# ----------------------------------------------------------------------------

def attention(q, k, v, *, causal: bool = True, use_kernel: bool = True,
              block_q: int = 128, block_k: int = 128,
              interpret: bool | None = None):
    """Tiled attention via the Pallas kernel, or the jnp reference when
    ``use_kernel=False``."""
    if not use_kernel:
        return ref.attention_ref(q, k, v, causal=causal)
    interp = default_interpret() if interpret is None else interpret
    return flash_attention(q, k, v, causal=causal, block_q=block_q,
                           block_k=block_k, interpret=interp)
