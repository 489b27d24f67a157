"""Embedding tables made on the device from the seed, block by block.

The law: a table of ``rows`` x ``dim`` is cut into equal blocks of
:func:`block_rows` rows, and block ``b`` holds
``normal(fold_in(key, b), (block, dim)) * std`` (the ``init="normal"`` law of
``MFConfig``, drawn per block so that no full-table fp32 temporary exists).
An int8 table quantizes each block as it is drawn: symmetric per-row absmax,
``scale = max(absmax / 127, 1e-12)``, ``q = clip(round(x / scale), -127,
127)``, a zero error-feedback residual with scale ``1e-12``.  The resident
tile of ``tile_size`` distinct sorted ids is ``sort(top_k(uniform(key,
(items,)), tile_size))``.

:func:`initial_rows` and :func:`change_norm` draw the blocks again, for the
reference and for the program's side of a comparison.

Each of these is a copy of the program's own law (``core/mf.init_mf``,
``optim/quantization.quantize_table``, ``core/samplers.sample_unique``), kept
here so that a change to the program cannot move the benchmark's inputs.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

TARGET_BLOCK = 1 << 20
SCALE_FLOOR = 1e-12


def block_rows(rows: int, target: int = TARGET_BLOCK) -> int:
    """The largest divisor of ``rows`` that is at most ``target``, found
    among ``rows / n`` for n = ceil(rows / target), ...; refuses a row count
    whose divisors near the target are all tiny."""
    n = -(-rows // target)
    while rows % n:
        n += 1
    block = rows // n
    if block * 8 < min(rows, target):
        raise ValueError(f"{rows} rows have no divisor near {target}")
    return block


def row_bytes(config: dict) -> int:
    """Bytes of one table row as served and updated: K fp32, or K int8 and
    one fp32 scale (an int8 table's error-feedback residual is training
    state, not counted)."""
    k = config["emb_dim"]
    return k + 4 if config["table_format"] == "int8" else 4 * k


def keys(seed_key: jax.Array):
    """(user key, item key, tile key) of a run."""
    ku, ki, kt = jax.random.split(seed_key, 3)
    return ku, ki, kt


def _block(key, b, block, dim, std):
    return jax.random.normal(jax.random.fold_in(key, b), (block, dim),
                             jnp.float32) * std


@partial(jax.jit, static_argnums=(1, 2, 3))
def normal_table(key: jax.Array, rows: int, dim: int, std: float) -> jax.Array:
    """fp32 (rows, dim) table by the block law."""
    block = block_rows(rows)

    def body(b, t):
        return jax.lax.dynamic_update_slice_in_dim(
            t, _block(key, b, block, dim, std), b * block, axis=0)
    return jax.lax.fori_loop(0, rows // block, body,
                             jnp.zeros((rows, dim), jnp.float32))


def quantize_rows(x: jax.Array):
    """Per-row absmax int8: (q, (R, 1) scale)."""
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(absmax / 127.0, SCALE_FLOOR).astype(jnp.float32)
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


@partial(jax.jit, static_argnums=(1, 2, 3))
def int8_rows(key: jax.Array, rows: int, dim: int, std: float):
    """(q, scale) of an int8 (rows, dim) table by the block law: each block
    is drawn in fp32 and quantized into preallocated arrays."""
    block = block_rows(rows)

    def body(b, carry):
        q, s = carry
        qb, sb = quantize_rows(_block(key, b, block, dim, std))
        return (jax.lax.dynamic_update_slice_in_dim(q, qb, b * block, 0),
                jax.lax.dynamic_update_slice_in_dim(s, sb, b * block, 0))
    return jax.lax.fori_loop(0, rows // block, body,
                             (jnp.zeros((rows, dim), jnp.int8),
                              jnp.zeros((rows, 1), jnp.float32)))


def int8_table(key: jax.Array, rows: int, dim: int, std: float):
    """(q, scale, err, err_scale): :func:`int8_rows` with the zero
    error-feedback residual of a freshly quantized table."""
    q, s = int8_rows(key, rows, dim, std)
    return (q, s, jnp.zeros((rows, dim), jnp.int8),
            jnp.full((rows, 1), SCALE_FLOOR, jnp.float32))


@partial(jax.jit, static_argnums=(1, 2))
def tile_ids(key: jax.Array, items: int, tile_size: int) -> jax.Array:
    """``tile_size`` distinct item ids, sorted ascending."""
    u = jax.random.uniform(key, (items,))
    return jnp.sort(jax.lax.top_k(u, tile_size)[1].astype(jnp.int32))


def _logical(x):
    """fp32 rows of a table block: an fp32 block as it is, an int8 one
    ``(q, scale, err, err_scale)`` as payload and error-feedback residual
    together, the value its next update starts from."""
    if isinstance(x, tuple):
        q, s, e, es = x
        return q.astype(jnp.float32) * s + e.astype(jnp.float32) * es
    return x


def _initial_block(key, b, block, dim, std, fmt):
    x = _block(key, b, block, dim, std)
    if fmt == "int8":
        q, s = quantize_rows(x)
        x = q.astype(jnp.float32) * s
    return x


@partial(jax.jit, static_argnums=(2, 3, 4, 5))
def initial_rows(key: jax.Array, ids: jax.Array, rows: int, dim: int,
                 std: float, fmt: str) -> jax.Array:
    """fp32 rows ``ids`` of the block-law table of ``key`` in format ``fmt``
    (an int8 table's rows dequantized), drawn block by block; an id out of
    range reads zeros."""
    block = block_rows(rows)

    def body(b, out):
        x = _initial_block(key, b, block, dim, std, fmt)
        local = ids - b * block
        hit = (local >= 0) & (local < block)
        return jnp.where(hit[:, None], x[jnp.clip(local, 0, block - 1)], out)
    return jax.lax.fori_loop(0, rows // block, body,
                             jnp.zeros((ids.shape[0], dim), jnp.float32))


@partial(jax.jit, static_argnums=(2, 3, 4))
def change_norm(table, key: jax.Array, dim: int, std: float,
                fmt: str) -> jax.Array:
    """||table - T0|| where T0 is the block-law table of ``key`` in format
    ``fmt`` (an int8 table read with its residual), computed block by block
    (no second full table on the device)."""
    rows = table[0].shape[0] if isinstance(table, tuple) else table.shape[0]
    block = block_rows(rows)

    def body(b, acc):
        cur = _logical(jax.tree_util.tree_map(
            lambda t: jax.lax.dynamic_slice_in_dim(t, b * block, block, 0),
            table))
        d = cur - _initial_block(key, b, block, dim, std, fmt)
        return acc + jnp.sum(d * d)
    return jnp.sqrt(jax.lax.fori_loop(0, rows // block, body,
                                      jnp.zeros((), jnp.float32)))
