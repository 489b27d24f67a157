"""Int8 embedding tables with per-row fp32 scales (the affordability lever).

HEAT's ceiling on users-per-device is table bytes (§4.2 exists because of
it).  This module stores an embedding table as a :class:`QuantizedTable` —
symmetric per-row absmax int8 payload + one fp32 scale per row — which cuts
the *serving/checkpoint* footprint to ``(K + 4) / (4K)`` of fp32 (~0.27x at
K=64, well under the "halved" gate in benchmarks/check.py).  Training carries
an additional int8 error-feedback residual per row (Seide et al., the same
idiom ``optim/compression.py`` proved out for gradients), so the full
training carry is ~2.1 bytes/element — still ~2x under fp32.

Layout-polymorphic accessors (:func:`gather_rows`, :func:`num_rows`,
:func:`slice_rows`, ...) let every consumer — the train step, the samplers,
retrieval, the divergence guard, serving — accept either a plain ``(R, K)``
array or a :class:`QuantizedTable` without branching at call sites.  The
invariant they all preserve: **the fp32 table is never materialized in the
hot path** — only gathered rows are dequantized (fused gather-multiply in
XLA, or inside the Pallas gather-dequant kernel on the kernel backend).

Updates (:func:`apply_updates` / :func:`apply_updates_many`) requantize only
the touched rows with **stochastic rounding** (``floor(x + u)``, unbiased)
keyed from the caller's ``(seed, step)`` rng stream, so the quantized
trajectory has the same bit-exact restart contract as fp32: restore the
carry, replay the steps, get identical int8 tables.  The rounding residual
is fed back into the next update of the same row (error feedback), keeping
the quantizer unbiased over time; the residual itself is int8-quantized so
it can ride the donated scan carry without doubling the table bytes.

Known staleness: the §4.2 tile write-through applies exact fp32 updates to
the replicated tile copy while the backing table rows are requantized, so
tile rows drift from the table by at most the per-row quantization error
until the next scheduled refresh re-gathers them — the same bounded-staleness
contract the tile already has for cross-shard reads.
"""
from __future__ import annotations

from typing import NamedTuple, Union

import jax
import jax.numpy as jnp

#: scale floor — keeps all-zero rows (absmax 0) from dividing by zero while
#: still dequantizing them to exact zeros (q is 0 wherever x is 0).
SCALE_FLOOR = 1e-12

#: the advertised table_format vocabulary (MFConfig.table_format).
TABLE_FORMATS = ("fp32", "int8")


class QuantizedTable(NamedTuple):
    """One embedding table in int8-with-per-row-scales form (a jit-friendly
    pytree, donated through scan carries exactly like a plain array).

    ``q``: (R, K) int8 payload; ``scale``: (R, 1) fp32 per-row scales
    (``row = q * scale``); ``err``/``err_scale``: the int8-quantized
    error-feedback residual of the last update of each row — training
    state, excluded from the serving-bytes accounting."""

    q: jax.Array
    scale: jax.Array
    err: jax.Array
    err_scale: jax.Array

    @property
    def shape(self) -> tuple[int, int]:
        """Logical (R, K) table shape."""
        return self.q.shape

    @property
    def dtype(self):
        """Logical element dtype (what dequantized rows come out as)."""
        return self.scale.dtype


Table = Union[jax.Array, QuantizedTable]


def _row_quantize(x: jax.Array):
    """Symmetric per-row absmax: (..., K) fp32 -> (int8, (..., 1) fp32)."""
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = (absmax / 127.0).clip(SCALE_FLOOR).astype(jnp.float32)
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def stochastic_round(x: jax.Array, rng: jax.Array) -> jax.Array:
    """Unbiased stochastic rounding to the integer grid: ``floor(x + u)``
    with ``u ~ U[0, 1)``, so ``E[round(x)] == x`` exactly — the property the
    quantized SGD trajectory needs to stay an unbiased estimator of the fp32
    one (property-tested in tests/test_quantization.py)."""
    u = jax.random.uniform(rng, x.shape, dtype=x.dtype)
    return jnp.floor(x + u)


def quantize_table(x: jax.Array) -> QuantizedTable:
    """fp32 (R, K) table -> :class:`QuantizedTable` (round-to-nearest, zero
    residual — the init / import path; training rounds stochastically)."""
    q, scale = _row_quantize(x.astype(jnp.float32))
    return QuantizedTable(
        q=q, scale=scale,
        err=jnp.zeros_like(q),
        err_scale=jnp.full_like(scale, SCALE_FLOOR))


def dequantize_rows(table: QuantizedTable, ids: jax.Array) -> jax.Array:
    """Gather + dequantize rows ``ids`` (any int shape) -> fp32
    ``ids.shape + (K,)`` — the fused form XLA turns into gather/multiply
    with no full-table temporary."""
    return table.q[ids].astype(jnp.float32) * table.scale[ids]


def dequantize_table(table: Table) -> jax.Array:
    """Full fp32 materialization — offline/eval paths only (the k-means index
    build, whole-table scoring); never call this in the training hot path."""
    if not isinstance(table, QuantizedTable):
        return table
    return table.q.astype(jnp.float32) * table.scale


def gather_rows(table: Table, ids: jax.Array, *,
                use_kernel: bool = False) -> jax.Array:
    """Layout-polymorphic row gather: ``table[ids]`` for a plain array,
    :func:`dequantize_rows` for a quantized one.  ``use_kernel=True`` routes
    a quantized gather through the Pallas gather-dequant kernel
    (kernels/embedding_update.py) — one scalar-prefetched row DMA per id,
    dequantized inside the kernel (the §4.3 access pattern for int8)."""
    if not isinstance(table, QuantizedTable):
        return table[ids]
    if use_kernel:
        from repro.kernels.embedding_update import gather_dequant_rows
        from repro.kernels.ops import default_interpret
        flat = ids.reshape(-1)
        rows = gather_dequant_rows(table.q, table.scale, flat,
                                   interpret=default_interpret())
        return rows.reshape(tuple(ids.shape) + (table.q.shape[1],))
    return dequantize_rows(table, ids)


def num_rows(table: Table) -> int:
    """Logical row count of either layout."""
    if isinstance(table, QuantizedTable):
        return table.q.shape[0]
    return table.shape[0]


def logical_dtype(table: Table):
    """The dtype dequantized/served rows come out as."""
    return table.dtype


def slice_rows(table: Table, start: int, stop: int) -> jax.Array:
    """Static row slice ``table[start:stop]`` as fp32-equivalent rows."""
    if not isinstance(table, QuantizedTable):
        return table[start:stop]
    return (table.q[start:stop].astype(jnp.float32) * table.scale[start:stop])


def table_spec(tree):
    """Hashable (treedef, leaf (shape, dtype) tuple) of a table pytree —
    what a compiled serving program is keyed on.  Distinguishes fp32 from
    int8 layouts *and* mismatched shapes, so ``BatchingRecommender`` can
    refuse a refresh that would retrace."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return (str(treedef),
            tuple((tuple(l.shape), str(jnp.dtype(l.dtype))) for l in leaves))


def table_nbytes(table: Table) -> int:
    """Serving/checkpoint bytes of the table proper: payload + scales for
    int8 (the error-feedback residual is optimizer state, counted by
    :func:`carry_nbytes`), plain nbytes for fp32."""
    if isinstance(table, QuantizedTable):
        return int(table.q.size) * table.q.dtype.itemsize \
            + int(table.scale.size) * table.scale.dtype.itemsize
    return int(table.size) * table.dtype.itemsize


def carry_nbytes(table: Table) -> int:
    """Total training-carry bytes (payload + scales + residual)."""
    if isinstance(table, QuantizedTable):
        return sum(int(l.size) * l.dtype.itemsize for l in table)
    return table_nbytes(table)


def table_all_finite(table: Table) -> jax.Array:
    """() bool — divergence-guard finiteness check.  Int8 payloads cannot
    hold NaN/inf, so only the fp32 scales need checking."""
    if isinstance(table, QuantizedTable):
        return (jnp.all(jnp.isfinite(table.scale))
                & jnp.all(jnp.isfinite(table.err_scale)))
    return jnp.all(jnp.isfinite(table))


def max_row_norm(table: Table) -> jax.Array:
    """() f32 — max L2 row norm of the *served* rows, computed without
    materializing the dequantized table (``scale_r * ||q_r||``)."""
    if isinstance(table, QuantizedTable):
        qn = jnp.sqrt(jnp.sum(
            table.q.astype(jnp.float32) ** 2, axis=-1))
        return jnp.max(table.scale[:, 0] * qn)
    return jnp.sqrt(jnp.max(jnp.sum(table * table, axis=-1)))


def _dedup(ids: jax.Array, grads: jax.Array):
    """Sorted segment-sum over duplicate ids (the §4.5 pre-reduction, same
    shape contract as kernels/ops.sparse_row_update): returns
    (unique-ids-per-lane, reduced grads, live-lane count)."""
    order = jnp.argsort(ids)
    sids = ids[order]
    sg = grads[order]
    first = jnp.concatenate([jnp.ones((1,), bool), sids[1:] != sids[:-1]])
    seg = jnp.cumsum(first) - 1
    reduced = jnp.zeros_like(sg).at[seg].add(sg)
    uids = jnp.zeros_like(sids).at[seg].max(sids)
    return uids, reduced, seg[-1] + 1


def apply_updates(table: QuantizedTable, ids: jax.Array, grads: jax.Array,
                  lr, rng: jax.Array) -> QuantizedTable:
    """SGD on the touched rows of a quantized table (the int8 analogue of the
    engine's ``row_update``): pre-reduce duplicate ids, dequantize the unique
    rows + their error-feedback residual, apply ``-lr * grad``, requantize
    with stochastic rounding, scatter the new payload/scale/residual back.

    ``rng`` must derive from the step's ``(seed, step)`` stream (the caller
    fold_ins a fixed salt) — the rounding draw is then a pure function of
    (seed, step), which is what keeps restarts bit-identical.
    """
    ids = ids.reshape(-1).astype(jnp.int32)
    grads = grads.reshape(-1, grads.shape[-1]).astype(jnp.float32)
    uids, g, live_n = _dedup(ids, grads)
    b = uids.shape[0]

    rows = dequantize_rows(table, uids)
    resid = table.err[uids].astype(jnp.float32) * table.err_scale[uids]
    new_rows = rows + resid - lr * g

    absmax = jnp.max(jnp.abs(new_rows), axis=-1, keepdims=True)
    new_scale = (absmax / 127.0).clip(SCALE_FLOOR).astype(jnp.float32)
    q_new = jnp.clip(stochastic_round(new_rows / new_scale, rng),
                     -127, 127).astype(jnp.int8)
    err = new_rows - q_new.astype(jnp.float32) * new_scale
    eq, escale = _row_quantize(err)

    # Dead lanes (duplicates collapsed by the pre-reduce) are dropped
    # out-of-bounds, like the kernel path's scatter.
    sids = jnp.where(jnp.arange(b) < live_n, uids, num_rows(table))
    return QuantizedTable(
        q=table.q.at[sids].set(q_new, mode="drop"),
        scale=table.scale.at[sids].set(new_scale, mode="drop"),
        err=table.err.at[sids].set(eq, mode="drop"),
        err_scale=table.err_scale.at[sids].set(escale, mode="drop"))


def apply_updates_many(table: QuantizedTable, groups, lr,
                       rng: jax.Array) -> QuantizedTable:
    """All of a step's gradient groups (pos/neg/history) in ONE pre-reduce +
    requantize pass — the quantized ``row_update_many``.  Cross-group
    duplicate ids reduce together, so each touched row is requantized exactly
    once per step (requantizing per group would compound rounding noise)."""
    ids = jnp.concatenate([i.reshape(-1) for i, _ in groups])
    grads = jnp.concatenate([g.reshape(-1, g.shape[-1]) for _, g in groups])
    return apply_updates(table, ids, grads, lr, rng)
