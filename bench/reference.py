"""Plain references of what the benchmark's cells compute, in straightforward
``jax.numpy`` at ``highest`` matmul precision.  They import nothing of the
program and take nothing it made: tables and data come from the benchmark's
own generators (``bench/traffic``), the random draws from copies of the
program's laws, written out below.

* :func:`mf_window` — ``n`` HEAT steps of matrix factorization with the
  sampled cosine contrastive loss (SimpleX Eq. 3): the batch derivation, the
  negatives drawn by slot from a resident tile of item ids, the loss and its
  gradients by autodiff, SGD on every touched row of both tables (duplicate
  ids accumulate), and the tile redrawn every ``refresh_interval`` steps.
  The tile is a coherent cache of the item table, so negatives are read from
  the table itself.
* :func:`topk_exact` — top-k items by cosine over an int8 or fp32 table
  (exactly for int8: each row's positive scale cancels in a cosine).
* :func:`topk_lowp` — the same scores from rows one precision lower (int8
  to int4, fp32 to bfloat16): the serving control.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-12


def _partitionable():
    # The program's random draws use JAX's partitionable threefry; the copies
    # of its laws below draw the same way.
    jax.config.update("jax_threefry_partitionable", True)


#: The precision one step below each table format's: the control's.
LOWER = {"int8": "int4", "fp32": "bfloat16"}


def _f32(x):
    """fp32 rows.  int8 rows are exact in fp32, and so are their dot products
    and squared norms at ``highest`` precision (K * 127^2 < 2^24), so a
    cosine of int8 rows is exact here: each row's positive scale cancels."""
    return x.astype(jnp.float32)


def _lower(x, lower):
    """Rows in the control's precision: ``int4`` re-quantizes each row to
    4 bits (per-row absmax), ``bfloat16`` rounds each element."""
    x = _f32(x)
    if lower == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    scale = jnp.max(jnp.abs(x), -1, keepdims=True).clip(EPS) / 7.0
    return jnp.round(x / scale) * scale


# ---------------------------------------------------------------------------
# Training
# ---------------------------------------------------------------------------

class MFSpec(NamedTuple):
    """Static sizes and hyperparameters of a training reference."""
    num_users: int
    num_items: int
    num_negatives: int
    tile_size: int
    refresh_interval: int
    batch_size: int
    lr: float
    mu: float
    theta: float


def batch_ids(train_pos, num_users, num_items, seed, step, batch_size):
    """Copy of the program's (seed, step) batch law: users and one train
    positive each, -1 slots resampled from column 0, empty rows uniform."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
    ku, kc = jax.random.split(key)
    users = jax.random.randint(ku, (batch_size,), 0, num_users, jnp.int32)
    cols = jax.random.randint(kc, (batch_size,), 0, train_pos.shape[1],
                              jnp.int32)
    pos = train_pos[users, cols]
    pos = jnp.where(pos >= 0, pos, train_pos[users, 0])
    uniform = jax.random.randint(jax.random.fold_in(key, 7), (batch_size,),
                                 0, num_items, jnp.int32)
    return users, jnp.where(pos >= 0, pos, uniform).astype(jnp.int32)


def _cos(a, b):
    an = jnp.sqrt(jnp.sum(a * a, -1)).clip(EPS)
    bn = jnp.sqrt(jnp.sum(b * b, -1)).clip(EPS)
    return jnp.sum(a * b, -1) / (an * bn)


def ccl_loss(u, p, negs, mu, theta):
    """SimpleX Eq. 3 averaged over the batch: (1 - cos(u, p)) +
    mu / n * sum_j relu(cos(u, n_j) - theta)."""
    pos = _cos(u, p)
    neg = _cos(u[:, None, :], negs)
    per = (1.0 - pos) + mu / negs.shape[1] * jnp.sum(
        jnp.maximum(neg - theta, 0.0), axis=-1)
    return jnp.mean(per)


class MFObservation(NamedTuple):
    """What a training comparison reads after the checked steps."""
    losses: np.ndarray          # (n,) per-step loss
    change: dict                # leaf -> norm of its change over the steps
    exact: dict                 # int leaf -> values


def touched_ids(train_pos, tile, tile_step, spec: MFSpec, seed: int,
                start: int, steps: int):
    """(user ids, item ids): the distinct rows of each table that ``steps``
    steps from ``start`` read or write (the batches' users and positives,
    and every tile they hold), sorted, padded at the end with the table's
    row count.  They depend on the seeds and the data alone."""
    _partitionable()
    return _touched(train_pos, tile, tile_step, jnp.int32(start), spec, seed,
                    steps)


def _refresh(tile, tstep, r_tile, spec: MFSpec):
    """The tile schedule: redrawn once ``refresh_interval`` steps have run."""
    refresh = tstep >= spec.refresh_interval - 1
    fresh = jnp.sort(jax.lax.top_k(
        jax.random.uniform(r_tile, (spec.num_items,)), spec.tile_size)[1]
        .astype(jnp.int32))
    return jnp.where(refresh, fresh, tile), jnp.where(refresh, 0, tstep + 1)


def _step_keys(seed, s, spec: MFSpec):
    """(negative slots, tile key) of step ``s``."""
    r_neg, r_tile = jax.random.split(
        jax.random.fold_in(jax.random.PRNGKey(seed), s))
    local = jax.random.randint(r_neg, (spec.batch_size, spec.num_negatives),
                               0, spec.tile_size, jnp.int32)
    return local, r_tile


@partial(jax.jit, static_argnums=(4, 5, 6))
def _touched(train_pos, tile0, tstep0, start, spec, seed, steps):
    def step_fn(carry, s):
        tile, tstep = carry
        users, pos = batch_ids(train_pos, spec.num_users, spec.num_items,
                               seed, s, spec.batch_size)
        tile, tstep = _refresh(tile, tstep, _step_keys(seed, s, spec)[1],
                               spec)
        return (tile, tstep), (users, pos, tile)

    _, (users, pos, tiles) = jax.lax.scan(
        step_fn, (tile0, tstep0), start + jnp.arange(steps, dtype=jnp.int32))
    items = jnp.concatenate([pos.reshape(-1), tile0, tiles.reshape(-1)])
    return (jnp.unique(users, size=users.size, fill_value=spec.num_users),
            jnp.unique(items, size=items.size, fill_value=spec.num_items))


def mf_window(user_ids, user0, item_ids, item0, tile, tile_step, train_pos,
              spec: MFSpec, seed: int, start: int, steps: int,
              lower=None) -> "MFObservation":
    """``steps`` reference steps from step index ``start``, on the rows
    ``user0`` and ``item0`` (fp32) of the ids :func:`touched_ids` gives:
    the other rows are neither read nor written.  ``lower`` (:data:`LOWER`)
    reads every row one precision lower, for the control: ``bfloat16`` also
    computes the step in bfloat16.  Returns an :class:`MFObservation`."""
    _partitionable()
    out = _mf_window(user_ids, user0, item_ids, item0, tile, tile_step,
                     train_pos, jnp.int32(start), spec, seed, steps, lower)
    losses, du, di, dt, tile_ids, tstep, step = jax.device_get(out)
    return MFObservation(
        losses=np.asarray(losses, np.float64),
        change={"user_table": float(du), "item_table": float(di),
                "tile_emb": float(dt)},
        exact={"tile_ids": np.asarray(tile_ids), "tile_step": int(tstep),
               "step": int(step)})


def _read(x, lower):
    if lower is None:
        return x
    if lower == "bfloat16":
        return x.astype(jnp.bfloat16)
    return _lower(x, lower)


@partial(jax.jit, static_argnums=(8, 9, 10, 11))
def _mf_window(user_ids, user0, item_ids, item0, tile0, tile_step0,
               train_pos, start, spec, seed, steps, lower):
    def step_fn(carry, s):
        user, item, tile, tstep = carry
        users, pos = batch_ids(train_pos, spec.num_users, spec.num_items,
                               seed, s, spec.batch_size)
        local, r_tile = _step_keys(seed, s, spec)
        cu = jnp.searchsorted(user_ids, users)
        cp = jnp.searchsorted(item_ids, pos)
        cn = jnp.searchsorted(item_ids, tile[local])
        u, p, ng = (_read(x, lower) for x in (user[cu], item[cp], item[cn]))
        loss, (gu, gp, gn) = jax.value_and_grad(ccl_loss, argnums=(0, 1, 2))(
            u, p, ng, spec.mu, spec.theta)
        f32 = jnp.float32
        user = user.at[cu].add(-spec.lr * gu.astype(f32))
        item = item.at[cp].add(-spec.lr * gp.astype(f32))
        item = item.at[cn.reshape(-1)].add(
            -spec.lr * gn.reshape(-1, gn.shape[-1]).astype(f32))
        tile, tstep = _refresh(tile, tstep, r_tile, spec)
        return (user, item, tile, tstep), loss.astype(f32)

    with jax.default_matmul_precision("highest"):
        (user, item, tile, tstep), losses = jax.lax.scan(
            step_fn, (user0, item0, tile0, tile_step0),
            start + jnp.arange(steps, dtype=jnp.int32))

    def norm(a, b):
        d = a - b
        return jnp.sqrt(jnp.sum(d * d))
    tile_rows = item[jnp.searchsorted(item_ids, tile)]
    tile_rows0 = item0[jnp.searchsorted(item_ids, tile0)]
    return (losses, norm(user, user0), norm(item, item0),
            norm(tile_rows, tile_rows0), tile, tstep, start + steps)


def compare_mf(prog: MFObservation, ref: MFObservation) -> dict:
    """The numbers a training cell compares:

    * ``loss_gap``: the largest relative gap of a step's loss;
    * ``change_gap``: over the float leaves, the largest gap between the
      program's and the reference's norm of the leaf's change, over the
      larger of that leaf's reference norm and the median leaf's.  Leaves
      whose reference change is under a thousandth of the median leaf's are
      left out (they move by round-off alone);
    * ``exact_mismatch``: elements of the integer leaves (tile ids, tile
      and step counters) that differ."""
    lg = np.abs(prog.losses - ref.losses) / np.maximum(np.abs(ref.losses),
                                                       EPS)
    loss_gap = float(np.max(lg)) if lg.size else float("nan")
    if not np.all(np.isfinite(prog.losses)):
        loss_gap = float("inf")
    med = float(np.median(list(ref.change.values())))
    gaps = [abs(prog.change[k] - r) / max(r, med)
            for k, r in ref.change.items() if r >= 1e-3 * med]
    change_gap = max(gaps) if gaps else float("nan")
    mism = 0
    for k, v in ref.exact.items():
        a, b = np.asarray(prog.exact[k]), np.asarray(v)
        mism += int(a.size) if a.shape != b.shape else int(np.sum(a != b))
    return {"loss_gap": loss_gap, "change_gap": change_gap,
            "exact_mismatch": float(mism)}


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------

ITEM_BLOCK = 1 << 18


def _norms(x):
    return jnp.sqrt(jnp.sum(x * x, axis=-1)).clip(EPS)


def _topk_blocks(u, items, k, rows_of):
    """(scores, ids) of the top-k cosines of users ``u`` (B, K) fp32 over
    ``items``, whose blocks ``rows_of`` turns into fp32 rows."""
    un = _norms(u)
    rows = items.shape[0]
    blocks = -(-rows // ITEM_BLOCK)
    iq = jnp.pad(items, ((0, blocks * ITEM_BLOCK - rows), (0, 0)))
    b = u.shape[0]

    def body(i, carry):
        best_s, best_i = carry
        blk = rows_of(jax.lax.dynamic_slice_in_dim(iq, i * ITEM_BLOCK,
                                                   ITEM_BLOCK, 0))
        with jax.default_matmul_precision("highest"):
            s = (u @ blk.T) / (un[:, None] * _norms(blk)[None])
        ids = i * ITEM_BLOCK + jnp.arange(ITEM_BLOCK, dtype=jnp.int32)
        s = jnp.where(ids[None] < rows, s, -jnp.inf)
        cs = jnp.concatenate([best_s, s], 1)
        ci = jnp.concatenate([best_i, jnp.broadcast_to(ids, s.shape)], 1)
        best_s, j = jax.lax.top_k(cs, k)
        return best_s, jnp.take_along_axis(ci, j, 1)

    return jax.lax.fori_loop(0, blocks, body,
                             (jnp.full((b, k), -jnp.inf, jnp.float32),
                              jnp.zeros((b, k), jnp.int32)))


@partial(jax.jit, static_argnums=(2,))
def topk_exact(users, items, k: int):
    """(scores, ids) of the top-k items by cosine, best first: rows as
    stored (int8 or fp32), scored at ``highest`` precision."""
    return _topk_blocks(_f32(users), items, k, _f32)


@jax.jit
def exact_scores(users, items, ids):
    """Cosines (B, k) of ``users`` (B, K) against items ``ids`` (B, k)."""
    rows = _f32(items[jnp.clip(ids, 0, items.shape[0] - 1)])    # (B, k, K)
    u = _f32(users)
    dots = jnp.einsum("bk,bjk->bj", u, rows, precision="highest")
    return dots / (_norms(u)[:, None] * _norms(rows))


@partial(jax.jit, static_argnums=(2, 3))
def topk_lowp(users, items, k: int, lower: str):
    """Top-k ids by cosine over rows in the ``lower`` precision
    (:data:`LOWER`), scored in fp32: the control of a serving cell."""
    return _topk_blocks(_lower(users, lower), items, k,
                        lambda blk: _lower(blk, lower))[1]


def topk_gap(users, items, served: np.ndarray, k: int) -> float:
    """The widest gap by which a served answer's item lies below the exact
    answer at its rank: per request, the served ids' exact cosines sorted
    best first, against the exact top-k scores; the largest difference over
    ranks and requests.  An answer with an id out of range, a repeated id or
    too few ids reads 2 (the whole range of a cosine)."""
    served = np.asarray(served)
    ref_s, _ = topk_exact(users, items, k)
    got = np.asarray(exact_scores(users, items,
                                  jnp.asarray(served, jnp.int32)))
    ref_s = np.asarray(ref_s)
    gaps = []
    n_items = items.shape[0]
    for r in range(served.shape[0]):
        ids = served[r]
        if (ids.shape[0] != k or np.any(ids < 0) or np.any(ids >= n_items)
                or np.unique(ids).size != k):
            gaps.append(2.0)
            continue
        gaps.append(float(np.max(ref_s[r] - np.sort(got[r])[::-1])))
    return max(gaps) if gaps else float("nan")
