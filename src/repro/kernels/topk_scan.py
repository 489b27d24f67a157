"""Pallas TPU kernel: exact top-k over a streamed item table.

The whole catalog is scanned by one kernel.  The grid walks the item table
in row blocks of ``chunks_per_step`` score chunks; the pipeline DMAs each
block HBM->VMEM while the previous one is scored.  Inside a step each
(B, chunk) tile is scored in turn on the MXU against the user rows, so the
live score tile never exceeds (B, chunk).  The running (B, k) top-k lives
in the output blocks, which stay in VMEM for the whole scan, with each
row's k-th score kept beside it as the row's threshold.  A chunk is merged
only when one of its scores beats its row's threshold.  The step's scores
wait in VMEM and whether any beats the step's first thresholds is tested
once per step, so most steps of a large catalog cost the scores and one
branch; thresholds only rise, so no chunk of a step without such a score
needs a merge.  A merge is k rounds of max extraction over the
(B, k + chunk) candidates, the running entries first and the chunk's in id
order, each round taking the lowest position among equal scores: the
order ``lax.top_k`` gives over the concatenation of the running top-k and
the chunk (``ref.topk_scan_ref``).

Scoring, for int8 tables (``scale`` given): the payload converted to
bfloat16 is exact (|q| <= 127), and the user rows come split into bfloat16
parts whose sum is the f32 row, stacked as one (parts * B, K) operand, so
one MXU product per chunk gives ``u . q`` with exact products and f32
accumulation.  A cosine divides by the item norm taken from the payload:
the exact integer sums of ``q * q`` on the MXU (an all-ones row against
the squares split into two exact bfloat16 parts); the per-row scales and
the user norm are positive per-row factors that cancel from every
comparison.  A dot multiplies by the scales, read lane-dense as a
(1, chunk) row.  For float tables the block is used as stored, and a
cosine's item norms come from the squares split into three bfloat16 parts
(an f32 sum).

The ragged end of the catalog is masked by item id (``id >= num_items``
scores -inf), so the table is not padded (only a catalog smaller than one
chunk is); an optional (B, I)
``exclude_mask`` is read per chunk alongside it.  The kernel also counts
the chunks it merged.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
#: Widest score chunk: (32, 512) f32 scores are 16 vector registers.
MAX_CHUNK = 512
#: Item payload bytes DMA'd per grid step (several chunks), to spread the
#: per-step cost of the pipeline.
STEP_BYTES = 512 * 1024
#: User rows per grid block; larger batches take several passes.
ROW_BLOCK = 128
#: bfloat16 parts of the user rows for int8 tables (sum = the f32 row).
USER_PARTS = 3
_NT = (((1,), (1,)), ((), ()))           # contract the last dims: a @ b.T


def chunk_width(item_chunk: int) -> int:
    """Score-chunk columns for ``item_chunk``: a multiple of the 128-lane
    tile, at most ``item_chunk`` where that allows one and at most
    MAX_CHUNK."""
    return max(LANES, min(MAX_CHUNK, item_chunk // LANES * LANES))


def _bf16_parts(x: jax.Array, n: int) -> list:
    """``n`` bfloat16 arrays whose f32 sum is ``x``, largest first (exact
    for n=3 and f32 ``x`` up to rounding of the last part; exact for n=2
    where ``x`` holds integers below 2**16)."""
    parts = []
    for _ in range(n):
        p = x.astype(jnp.bfloat16)
        parts.append(p)
        x = x - p.astype(jnp.float32)
    return parts


def _sq_norms(x: jax.Array, parts: int) -> jax.Array:
    """(1, C) lane-dense sums of squares of the (C, K) rows of ``x``."""
    sq = x.astype(jnp.float32)
    sq = sq * sq
    ones = jnp.ones((8, x.shape[1]), jnp.bfloat16)
    acc = None
    for p in _bf16_parts(sq, parts):
        d = jax.lax.dot_general(ones, p, _NT,
                                preferred_element_type=jnp.float32)
        acc = d if acc is None else acc + d
    return acc[0:1, :]


def _chunk_scores(lhs, x, scale, *, rows, cosine):
    """(rows, C) scores of one chunk ``x`` (C, K) against ``lhs``: the user
    rows, or for int8 their stacked bfloat16 parts."""
    if x.dtype == jnp.int8:
        d = jax.lax.dot_general(lhs, x.astype(jnp.bfloat16), _NT,
                                preferred_element_type=jnp.float32)
        s = d[0:rows]
        for i in range(1, d.shape[0] // rows):
            s = s + d[i * rows:(i + 1) * rows]
        if not cosine:
            return s * scale
        n2 = _sq_norms(x, 2)
    else:
        s = jax.lax.dot_general(lhs, x.astype(jnp.float32), _NT,
                                preferred_element_type=jnp.float32)
        if not cosine:
            return s
        n2 = _sq_norms(x, 3)
    return s * (1.0 / jnp.sqrt(jnp.maximum(n2, 1e-24)))


def _merge(s, ids, best_s_ref, best_i_ref, thr_ref, k):
    """k rounds of max extraction over the running entries and the chunk's
    (rows, C) scores ``s`` with their (1, C) item ``ids``."""
    rows, kp = best_s_ref.shape
    cand_s = jnp.concatenate([best_s_ref[...], s], axis=1)
    cand_i = jnp.concatenate(
        [best_i_ref[...], jnp.broadcast_to(ids, s.shape)], axis=1)
    width = cand_s.shape[1]
    pos = jax.lax.broadcasted_iota(jnp.int32, cand_s.shape, 1)
    slot = jax.lax.broadcasted_iota(jnp.int32, (rows, kp), 1)

    # Round j takes the largest score, the lowest position among equals,
    # among the candidates that follow round j-1's pick (m, p) in that
    # order; lanes k..kp-1 of the running entries are not candidates.
    live = (pos < k) | (pos >= kp)

    def extract(j, carry):
        m, p, out_s, out_i = carry
        after = (cand_s < m) | ((cand_s == m) & (pos > p))
        v = jnp.where(live & after, cand_s, -jnp.inf)
        m = jnp.max(v, axis=1, keepdims=True)
        p = jnp.min(jnp.where(live & after & (cand_s == m), pos, width),
                    axis=1, keepdims=True)
        i = jnp.sum(jnp.where(pos == p, cand_i, 0), axis=1, keepdims=True)
        out_s = jnp.where(slot == j, m, out_s)
        out_i = jnp.where(slot == j, i, out_i)
        return m, p, out_s, out_i

    init = (jnp.full((rows, 1), jnp.inf, jnp.float32),
            jnp.full((rows, 1), -1, jnp.int32),
            jnp.full((rows, kp), -jnp.inf, jnp.float32),
            jnp.zeros((rows, kp), jnp.int32))
    kth, _, out_s, out_i = jax.lax.fori_loop(0, k, extract, init)
    best_s_ref[...] = out_s
    best_i_ref[...] = out_i
    thr_ref[...] = kth


def _kernel(*refs, k, chunk, chunks_per_step, num_items, rows, cosine,
            has_scale, has_mask):
    refs = list(refs)
    lhs_ref, items_ref = refs.pop(0), refs.pop(0)
    scale_ref = refs.pop(0) if has_scale else None
    mask_ref = refs.pop(0) if has_mask else None
    best_i_ref, best_s_ref, merged_ref, thr_ref, scores_ref = refs
    step = pl.program_id(1)

    @pl.when((pl.program_id(0) == 0) & (step == 0))
    def _():
        merged_ref[0, 0] = 0

    @pl.when(step == 0)
    def _():
        best_s_ref[...] = jnp.full(best_s_ref.shape, -jnp.inf, jnp.float32)
        best_i_ref[...] = jnp.zeros(best_i_ref.shape, jnp.int32)
        thr_ref[...] = jnp.full(thr_ref.shape, -jnp.inf, jnp.float32)

    # One scalar branch per step, not per chunk: the chunks' MXU and VPU
    # work then runs back to back.
    lhs = lhs_ref[...]
    thr = thr_ref[...]
    first = step * chunks_per_step * chunk             # the step's first id
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
    hit = jnp.zeros((rows, chunk), jnp.float32)
    for j in range(chunks_per_step):
        cols = slice(j * chunk, (j + 1) * chunk)
        s = _chunk_scores(lhs, items_ref[cols, :],
                          scale_ref[:, cols] if has_scale else None,
                          rows=rows, cosine=cosine)
        s = jnp.where(first + j * chunk + iota >= num_items, -jnp.inf, s)
        if has_mask:
            s = jnp.where(mask_ref[:, cols] != 0, -jnp.inf, s)
        scores_ref[j] = s
        hit = jnp.maximum(hit, jnp.where(s > thr, 1.0, 0.0))

    @pl.when(jnp.max(hit) > 0.0)
    def _():
        def merge(j, carry):
            s = scores_ref[j]

            @pl.when(jnp.max(jnp.where(s > thr_ref[...], 1.0, 0.0)) > 0.0)
            def _():
                _merge(s, first + j * chunk + iota, best_s_ref, best_i_ref,
                       thr_ref, k)
                merged_ref[0, 0] += 1

            return carry

        jax.lax.fori_loop(0, chunks_per_step, merge, 0)


@functools.partial(jax.jit, static_argnames=(
    "k", "similarity", "item_chunk", "interpret"))
def topk_scan_pallas(u: jax.Array, items: jax.Array, scale, exclude_mask, *,
                     k: int, similarity: str, item_chunk: int,
                     interpret: bool = False):
    """Top-k item ids of every user row over the whole table.

    u: (B, K) f32 user rows; items: (I, K) int8 payload with ``scale``
    (I, 1) f32, or a float table with ``scale`` None; ``exclude_mask``
    (B, I) bool or None.  ``k`` <= I.  Returns ((B, k) int32 ids, the
    number of (user block, chunk) pairs merged as an int32 scalar).
    """
    b, dim = u.shape
    num_items = items.shape[0]
    cosine = similarity == "cosine"
    quantized = items.dtype == jnp.int8
    c = chunk_width(item_chunk)
    kp = pl.cdiv(k, LANES) * LANES
    rows = min(ROW_BLOCK, pl.cdiv(b, 8) * 8)
    bp = pl.cdiv(b, rows) * rows
    per_step = max(1, STEP_BYTES // (c * dim * items.dtype.itemsize))
    per_step = min(per_step, max(1, num_items // c))
    r = per_step * c
    if num_items < r:                    # a catalog smaller than one chunk
        items = jnp.pad(items, ((0, r - num_items), (0, 0)))
    width = max(num_items, r)

    u = jnp.pad(u.astype(jnp.float32), ((0, bp - b), (0, 0)), mode="edge")
    if quantized:
        parts = _bf16_parts(u, USER_PARTS)
        lhs = jnp.stack(parts).reshape(USER_PARTS, bp // rows, rows, dim)
        lhs = lhs.transpose(1, 0, 2, 3).reshape(-1, dim)
        lhs_rows = USER_PARTS * rows
    else:
        lhs, lhs_rows = u, rows
    args = [lhs, items]
    in_specs = [pl.BlockSpec((lhs_rows, dim), lambda i, g: (i, 0)),
                pl.BlockSpec((r, dim), lambda i, g: (g, 0))]
    has_scale = quantized and not cosine
    if has_scale:
        row = scale.reshape(1, -1).astype(jnp.float32)
        args.append(jnp.pad(row, ((0, 0), (0, width - num_items))))
        in_specs.append(pl.BlockSpec((1, r), lambda i, g: (0, g)))
    if exclude_mask is not None:
        mask = jnp.pad(exclude_mask.astype(jnp.int8),
                       ((0, bp - b), (0, width - num_items)), mode="edge")
        args.append(mask)
        in_specs.append(pl.BlockSpec((rows, r), lambda i, g: (i, g)))

    kernel = functools.partial(
        _kernel, k=k, chunk=c, chunks_per_step=per_step,
        num_items=num_items, rows=rows, cosine=cosine, has_scale=has_scale,
        has_mask=exclude_mask is not None)
    best = pl.BlockSpec((rows, kp), lambda i, g: (i, 0))
    ids, _, merged = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(bp, rows), pl.cdiv(width, r)),
        in_specs=in_specs,
        out_specs=[best, best,
                   pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_shape=[jax.ShapeDtypeStruct((bp, kp), jnp.int32),
                   jax.ShapeDtypeStruct((bp, kp), jnp.float32),
                   jax.ShapeDtypeStruct((1, 1), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((rows, 1), jnp.float32),
                        pltpu.VMEM((per_step, rows, c), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(*args)
    return ids[:b, :k], merged[0, 0]
