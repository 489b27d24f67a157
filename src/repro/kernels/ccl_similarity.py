"""Pallas TPU kernel: fused CCL similarity statistics (paper §4.3 + §4.4).

One VMEM pass per batch tile computes every dot/norm the CCL loss and its
analytic backward need:

    uu = ||u||^2, pp = ||p||^2, up = u.p, nn_j = ||n_j||^2, un_j = u.n_j

This is the TPU adaptation of HEAT's "vector products without concat/reshape":
the user/pos/neg blocks are tiled HBM->VMEM once, the (Bt,K)x(K,n) negative
contraction runs on the MXU, and no normalized or concatenated intermediate is
ever materialized in HBM.  A second kernel evaluates the fused backward from
the cached statistics (the §4.4 reuse — no dot product is recomputed).

Tiling: grid over batch tiles of ``block_b`` rows.  Blocks span the whole
n and K axes, so any n and K lower; the wrappers in ops.py pad only the batch
rows, to a multiple of ``block_b``.  The per-example (Bt, n, K) negatives
block dominates VMEM: it is double-buffered on the way in, the backward
double-buffers a same-sized gradient block on the way out, and the
multiply-and-reduce temporaries are that size again.  ``per_example_block_b``
therefore sizes the tile so one negatives block is at most 1 MiB (32 rows at
n=64, K=128: about 8 MiB in all, inside the 16 MiB default scoped VMEM of a
v5e).  The per-example contractions (u . n_j, sum_j w_j n_j) are
multiply-and-reduce on the VPU: a batched ``dot_general`` with a 2-D operand
that has no free dimension does not lower on the TPU, and each of those dots
reads every negative once anyway, so they are bandwidth-bound either way.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _stats_kernel(u_ref, p_ref, n_ref, uu_ref, pp_ref, up_ref, nn_ref, un_ref):
    u = u_ref[...].astype(jnp.float32)          # (Bt, K)
    p = p_ref[...].astype(jnp.float32)          # (Bt, K)
    n = n_ref[...].astype(jnp.float32)          # (Bt, n, K)
    uu_ref[...] = jnp.sum(u * u, axis=-1, keepdims=True)       # (Bt, 1)
    pp_ref[...] = jnp.sum(p * p, axis=-1, keepdims=True)
    up_ref[...] = jnp.sum(u * p, axis=-1, keepdims=True)
    nn_ref[...] = jnp.sum(n * n, axis=-1)                      # (Bt, n)
    # un[b, j] = sum_k u[b, k] n[b, j, k]
    un_ref[...] = jnp.sum(u[:, None, :] * n, axis=-1)


def per_example_block_b(n: int, k: int) -> int:
    """Batch rows per tile for the per-example kernels: the largest multiple
    of 8 whose (rows, n, K) fp32 negatives block fits in 1 MiB (at least 8)."""
    return max(8, (1 << 20) // (n * k * 4) // 8 * 8)


def ccl_stats_pallas(user: jax.Array, pos: jax.Array, negs: jax.Array,
                     *, block_b: int | None = None, interpret: bool = False):
    """user (B,K), pos (B,K), negs (B,n,K) -> (uu, pp, up) (B,1) and (nn, un) (B,n)."""
    b, k = user.shape
    n = negs.shape[1]
    block_b = min(block_b or per_example_block_b(n, k), b)
    grid = (pl.cdiv(b, block_b),)
    out_shape = [
        jax.ShapeDtypeStruct((b, 1), jnp.float32),   # uu
        jax.ShapeDtypeStruct((b, 1), jnp.float32),   # pp
        jax.ShapeDtypeStruct((b, 1), jnp.float32),   # up
        jax.ShapeDtypeStruct((b, n), jnp.float32),   # nn
        jax.ShapeDtypeStruct((b, n), jnp.float32),   # un
    ]
    vec_spec = pl.BlockSpec((block_b, k), lambda i: (i, 0))
    neg_spec = pl.BlockSpec((block_b, n, k), lambda i: (i, 0, 0))
    scal_spec = pl.BlockSpec((block_b, 1), lambda i: (i, 0))
    row_spec = pl.BlockSpec((block_b, n), lambda i: (i, 0))
    return pl.pallas_call(
        _stats_kernel,
        grid=grid,
        in_specs=[vec_spec, vec_spec, neg_spec],
        out_specs=[scal_spec, scal_spec, scal_spec, row_spec, row_spec],
        out_shape=out_shape,
        interpret=interpret,
    )(user, pos, negs)


def _bwd_kernel(mu, theta, inv_n_negs,
                u_ref, p_ref, n_ref, uu_ref, pp_ref, up_ref, nn_ref, un_ref,
                g_ref, du_ref, dp_ref, dn_ref):
    """Analytic Eq. 4/5 backward from cached stats — zero recomputed dots.

    g_ref: (1, 1) scalar cotangent of the mean loss (already / batch outside).
    """
    eps = 1e-12
    u = u_ref[...].astype(jnp.float32)
    p = p_ref[...].astype(jnp.float32)
    negs = n_ref[...].astype(jnp.float32)
    uu = uu_ref[...] + eps                      # (Bt, 1)
    pp = pp_ref[...] + eps
    up = up_ref[...]
    nn = nn_ref[...] + eps                      # (Bt, n)
    un = un_ref[...]
    g = g_ref[0, 0]

    inv_u = jax.lax.rsqrt(uu)
    inv_p = jax.lax.rsqrt(pp)
    inv_nn = jax.lax.rsqrt(nn)

    neg_sim = un * inv_u * inv_nn
    d_ps = -g                                               # d loss/d pos_sim (per row)
    d_ns = (g * mu * inv_n_negs) * (neg_sim > theta).astype(jnp.float32)

    wp = d_ps * inv_u * inv_p                               # (Bt, 1)
    wn = d_ns * inv_u * inv_nn                              # (Bt, n)

    coeff_u = (wp * up + jnp.sum(wn * un, axis=-1, keepdims=True)) / uu
    # du = wp*p + sum_j wn_j negs_j - coeff_u * u
    wn_negs = jnp.sum(wn[..., None] * negs, axis=1)
    du_ref[...] = (wp * p + wn_negs - coeff_u * u).astype(du_ref.dtype)
    dp_ref[...] = (wp * u - (wp * up / pp) * p).astype(dp_ref.dtype)
    dn_ref[...] = (wn[..., None] * u[:, None, :]
                   - (wn * un / nn)[..., None] * negs).astype(dn_ref.dtype)


def _stats_shared_kernel(u_ref, p_ref, n_ref, uu_ref, pp_ref, up_ref, nn_ref,
                         un_ref):
    """Stats for the step-shared negative layout: the (n, K) negative block is
    resident in VMEM for every grid step and contracted against each (Bt, K)
    row tile on the MXU — the LM-head analogue of the per-example kernel."""
    u = u_ref[...].astype(jnp.float32)          # (Bt, K)
    p = p_ref[...].astype(jnp.float32)          # (Bt, K)
    n = n_ref[...].astype(jnp.float32)          # (n, K), shared
    uu_ref[...] = jnp.sum(u * u, axis=-1, keepdims=True)       # (Bt, 1)
    pp_ref[...] = jnp.sum(p * p, axis=-1, keepdims=True)
    up_ref[...] = jnp.sum(u * p, axis=-1, keepdims=True)
    nn_ref[...] = jnp.sum(n * n, axis=-1)[None, :]             # (1, n)
    un_ref[...] = jax.lax.dot_general(
        u, n, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                    # (Bt, n)


def ccl_stats_shared_pallas(user: jax.Array, pos: jax.Array, negs: jax.Array,
                            *, block_b: int = 256, interpret: bool = False):
    """user (T,K), pos (T,K), negs (n,K) -> (uu, pp, up) (T,1), nn (1,n), un (T,n)."""
    t, k = user.shape
    n = negs.shape[0]
    block_b = min(block_b, t)
    grid = (pl.cdiv(t, block_b),)
    out_shape = [
        jax.ShapeDtypeStruct((t, 1), jnp.float32),   # uu
        jax.ShapeDtypeStruct((t, 1), jnp.float32),   # pp
        jax.ShapeDtypeStruct((t, 1), jnp.float32),   # up
        jax.ShapeDtypeStruct((1, n), jnp.float32),   # nn
        jax.ShapeDtypeStruct((t, n), jnp.float32),   # un
    ]
    vec_spec = pl.BlockSpec((block_b, k), lambda i: (i, 0))
    neg_spec = pl.BlockSpec((n, k), lambda i: (0, 0))
    scal_spec = pl.BlockSpec((block_b, 1), lambda i: (i, 0))
    nn_spec = pl.BlockSpec((1, n), lambda i: (0, 0))
    row_spec = pl.BlockSpec((block_b, n), lambda i: (i, 0))
    return pl.pallas_call(
        _stats_shared_kernel,
        grid=grid,
        in_specs=[vec_spec, vec_spec, neg_spec],
        out_specs=[scal_spec, scal_spec, scal_spec, nn_spec, row_spec],
        out_shape=out_shape,
        interpret=interpret,
    )(user, pos, negs)


def _bwd_shared_kernel(mu, theta, inv_n_negs,
                       u_ref, p_ref, n_ref, uu_ref, pp_ref, up_ref, nn_ref,
                       un_ref, w_ref, g_ref, du_ref, dp_ref, dn_ref):
    """Analytic weighted backward for the shared layout.

    Per-row cotangents carry the reduction weight ``w`` (so padded/masked rows
    contribute exactly zero), and the shared negatives' gradient is summed
    across row tiles by revisiting the same (n, K) output block every grid
    step (initialize at step 0, accumulate after — the TPU grid is
    sequential, and interpret mode preserves the ordering).
    """
    eps = 1e-12
    u = u_ref[...].astype(jnp.float32)          # (Bt, K)
    p = p_ref[...].astype(jnp.float32)
    negs = n_ref[...].astype(jnp.float32)       # (n, K)
    uu = uu_ref[...] + eps                      # (Bt, 1)
    pp = pp_ref[...] + eps
    up = up_ref[...]
    nn = nn_ref[...] + eps                      # (1, n)
    un = un_ref[...]                            # (Bt, n)
    w = w_ref[...]                              # (Bt, 1)
    g = g_ref[0, 0]

    inv_u = jax.lax.rsqrt(uu)
    inv_p = jax.lax.rsqrt(pp)
    inv_nn = jax.lax.rsqrt(nn)                  # (1, n)

    pos_sim = up * inv_u * inv_p                # (Bt, 1)
    neg_sim = un * inv_u * inv_nn               # (Bt, n)
    d_ps = -g * w                               # (Bt, 1)
    d_ns = (g * mu * inv_n_negs) * w * (neg_sim > theta).astype(jnp.float32)

    u_hat = u * inv_u
    p_hat = p * inv_p
    wn = d_ns * inv_nn                          # (Bt, n)
    coeff = d_ps * pos_sim + jnp.sum(d_ns * neg_sim, axis=-1, keepdims=True)
    wn_negs = jax.lax.dot_general(
        wn, negs, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)     # (Bt, K)
    du_ref[...] = (inv_u * (d_ps * p_hat - coeff * u_hat)
                   + inv_u * wn_negs).astype(du_ref.dtype)
    dp_ref[...] = ((d_ps * inv_p) * (u_hat - pos_sim * p_hat)).astype(dp_ref.dtype)

    # Shared-negative gradient: this tile's Eq. 5 contributions, accumulated.
    part = jax.lax.dot_general(
        wn, u_hat, dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)     # (n, K) = wn.T @ u_hat
    col = jnp.sum(wn * neg_sim, axis=0)         # (n,)
    contrib = part - (col * inv_nn[0])[:, None] * negs

    @pl.when(pl.program_id(0) == 0)
    def _init():
        dn_ref[...] = jnp.zeros_like(dn_ref)

    dn_ref[...] += contrib.astype(dn_ref.dtype)


def ccl_bwd_shared_pallas(user, pos, negs, uu, pp, up, nn, un, w, g_scalar,
                          *, mu: float, theta: float,
                          block_b: int = 256, interpret: bool = False):
    """Fused weighted backward for the shared layout.

    w: (T, 1) normalized row weights (0 on padded rows); g_scalar: () raw
    cotangent of the weighted-sum loss (weights already fold the 1/T).
    Returns (du (T,K), dp (T,K), dn (n,K)).
    """
    t, k = user.shape
    n = negs.shape[0]
    block_b = min(block_b, t)
    grid = (pl.cdiv(t, block_b),)
    vec_spec = pl.BlockSpec((block_b, k), lambda i: (i, 0))
    neg_spec = pl.BlockSpec((n, k), lambda i: (0, 0))
    scal_spec = pl.BlockSpec((block_b, 1), lambda i: (i, 0))
    nn_spec = pl.BlockSpec((1, n), lambda i: (0, 0))
    row_spec = pl.BlockSpec((block_b, n), lambda i: (i, 0))
    g2d = g_scalar.reshape(1, 1).astype(jnp.float32)
    kernel = functools.partial(_bwd_shared_kernel, mu, theta, 1.0 / n)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[vec_spec, vec_spec, neg_spec,
                  scal_spec, scal_spec, scal_spec, nn_spec, row_spec,
                  scal_spec, pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_specs=[vec_spec, vec_spec, neg_spec],
        out_shape=[jax.ShapeDtypeStruct(user.shape, user.dtype),
                   jax.ShapeDtypeStruct(pos.shape, pos.dtype),
                   jax.ShapeDtypeStruct(negs.shape, jnp.float32)],
        interpret=interpret,
    )(user, pos, negs, uu, pp, up, nn, un, w, g2d)


def ccl_bwd_pallas(user, pos, negs, uu, pp, up, nn, un, g_scalar,
                   *, mu: float, theta: float,
                   block_b: int | None = None, interpret: bool = False):
    """Fused backward tile kernel.  g_scalar: () cotangent already divided by B."""
    b, k = user.shape
    n = negs.shape[1]
    block_b = min(block_b or per_example_block_b(n, k), b)
    grid = (pl.cdiv(b, block_b),)
    vec_spec = pl.BlockSpec((block_b, k), lambda i: (i, 0))
    neg_spec = pl.BlockSpec((block_b, n, k), lambda i: (i, 0, 0))
    scal_spec = pl.BlockSpec((block_b, 1), lambda i: (i, 0))
    row_spec = pl.BlockSpec((block_b, n), lambda i: (i, 0))
    g2d = g_scalar.reshape(1, 1).astype(jnp.float32)
    kernel = functools.partial(_bwd_kernel, mu, theta, 1.0 / n)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[vec_spec, vec_spec, neg_spec,
                  scal_spec, scal_spec, scal_spec, row_spec, row_spec,
                  pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_specs=[vec_spec, vec_spec, neg_spec],
        out_shape=[jax.ShapeDtypeStruct(user.shape, user.dtype),
                   jax.ShapeDtypeStruct(pos.shape, pos.dtype),
                   jax.ShapeDtypeStruct(negs.shape, negs.dtype)],
        interpret=interpret,
    )(user, pos, negs, uu, pp, up, nn, un, g2d)
