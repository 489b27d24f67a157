"""Implicit-feedback training data made on the device, by the law of the
program's host generator ``data/pipeline.synth_cf_dataset`` (copied here, so
that a change to the program cannot move the benchmark's inputs):

* every user and every item falls in one of ``clusters`` clusters, uniformly;
* a cluster's pool is its items in id order; the item of rank r is drawn
  with probability proportional to 1/(r+1) (Zipf-skewed positives, the hot
  ids spread over the catalog);
* each user keeps the first ``columns`` distinct items of its draws, in draw
  order, as its train positives.

The host generator redraws a user's row until it has enough distinct items.
Here each user makes ``candidates`` draws at once; a row with fewer distinct
items keeps -1 in its last columns (the batch derivation then resamples
column 0).  With 48 draws for 16 columns over pools of some 10^5 items no
row falls short in practice.

A draw inverts the law's cumulative weights in closed form: the rank r is
the number of m in [1, n] with H(m) <= u H(n) (n the pool size, H the
harmonic numbers, computed elementwise), found from
exp(u H(n) - gamma) and corrected by a few evaluations of H.  The distinct
items of a row are kept by two sorts along the row.  Neither step gathers
from or scatters into a large table, which on the chip would make set-up
take minutes.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

USER_BLOCK = 1 << 18
SHIFT = 8
EULER_GAMMA = 0.5772156649015329
CORRECTIONS = 3


def harmonic(m, xp=jnp):
    """H(m) for integer m >= 1, elementwise, in the float of the array
    module ``xp`` (float32 under ``jax.numpy``, float64 under ``numpy``):
    the asymptotic series at m + SHIFT (error < 1e-8 there) less the SHIFT
    terms between."""
    mf = xp.maximum(m, 1) * 1.0
    x = mf + SHIFT
    between = sum(1.0 / (mf + j) for j in range(1, SHIFT + 1))
    return (xp.log(x) + EULER_GAMMA + 0.5 / x - 1.0 / (12.0 * x * x)
            + 1.0 / (120.0 * x ** 4) - between)


def zipf_rank(u, n, xp=jnp):
    """Rank in [0, n) with P(r) proportional to 1/(r+1) (Zipf(1)), from
    uniform ``u`` and pool size ``n`` (broadcast): #{m in [1, n] : H(m) <=
    u H(n)}, clamped to n - 1.  One law for the device data (``jax.numpy``)
    and the host's request schedule (``numpy``)."""
    target = u * harmonic(n, xp)
    m = xp.floor(xp.exp(target - EULER_GAMMA)).astype(xp.int32)
    m = xp.clip(m, 0, n)
    for _ in range(CORRECTIONS):
        m = xp.where((m < n) & (harmonic(m + 1, xp) <= target), m + 1, m)
        m = xp.where((m >= 1) & (harmonic(m, xp) > target), m - 1, m)
    return xp.minimum(m, n - 1)


def first_distinct(items: jax.Array, columns: int) -> jax.Array:
    """The first ``columns`` distinct values of each row in row order, -1
    where a row has fewer."""
    rows, width = items.shape
    at = jnp.broadcast_to(jnp.arange(width, dtype=jnp.int32), items.shape)
    ranked, at = jax.lax.sort((items, at), dimension=1, num_keys=1,
                              is_stable=True)
    new = jnp.concatenate([jnp.ones((rows, 1), bool),
                           ranked[:, 1:] != ranked[:, :-1]], axis=1)
    # first occurrences ordered by where they were drawn; repeats last
    _, kept = jax.lax.sort((jnp.where(new, at, width), ranked), dimension=1,
                           num_keys=1)
    n = jnp.sum(new, axis=1, keepdims=True)
    return jnp.where(jnp.arange(columns) < n, kept[:, :columns], -1)


@partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _generate(key, num_users, num_items, clusters, columns, candidates):
    k_item, k_user, k_draw = jax.random.split(key, 3)
    item_cluster = jax.random.randint(k_item, (num_items,), 0, clusters,
                                      jnp.int32)
    user_cluster = jax.random.randint(k_user, (num_users,), 0, clusters,
                                      jnp.int32)
    order = jnp.argsort(item_cluster, stable=True).astype(jnp.int32)
    counts = jnp.sum(item_cluster[:, None] == jnp.arange(clusters), axis=0,
                     dtype=jnp.int32)
    offsets = jnp.cumsum(counts) - counts

    blocks = -(-num_users // USER_BLOCK)
    padded = blocks * USER_BLOCK
    ucl = jnp.pad(user_cluster, (0, padded - num_users))

    def block(b, out):
        c = jax.lax.dynamic_slice_in_dim(ucl, b * USER_BLOCK, USER_BLOCK)
        n = counts[c][:, None]                                    # pool size
        u = jax.random.uniform(jax.random.fold_in(k_draw, b),
                               (USER_BLOCK, candidates), jnp.float32)
        items = order[offsets[c][:, None] + zipf_rank(u, n)]      # (blk, D)
        return jax.lax.dynamic_update_slice_in_dim(
            out, first_distinct(items, columns), b * USER_BLOCK, 0)

    out = jax.lax.fori_loop(0, blocks, block,
                            jnp.full((padded, columns), -1, jnp.int32))
    return out[:num_users]


def generate(seed: int, num_users: int, num_items: int, *, clusters: int,
             columns: int, candidates: int) -> jax.Array:
    """train_pos (U, columns) int32 on the device, from ``seed``."""
    return _generate(jax.random.PRNGKey(seed), num_users, num_items,
                     clusters, columns, candidates)
