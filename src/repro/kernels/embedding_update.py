"""Pallas TPU kernel: fused gather + SGD row update (paper §3.1 / §4.5).

HEAT updates only the embedding rows touched by the current iteration.  The
hot loop is irregular: gather row ``ids[i]`` from the HBM-resident table,
fma with its gradient, write the new value.  This kernel implements the
gather+fma with **scalar-prefetched row indices**: the ids land in SMEM before
the grid runs, and each grid step copies ``ROWS_PER_STEP`` table rows
HBM->VMEM with one DMA per row, all in flight at once — the TPU version of
"each thread reads its corresponding embeddings" (§4.3), with the DMA engine
playing the role of the cache-friendly access pattern.

Block shapes: the TPU takes a block only if its last two dimensions are
multiples of (8, 128) or span the whole array, so a one-row block of an
(R, K) table cannot lower, squeezed or not.  The table therefore stays in HBM
(``memory_space=pl.ANY``) and each step gathers its rows into a
(ROWS_PER_STEP, K) VMEM buffer; gradients and outputs are ordinary
(ROWS_PER_STEP, K) blocks.  The wrappers pad the id list to a multiple of
ROWS_PER_STEP with id 0 and slice the padding back off.

Conflict handling (§4.5): the wrapper in ops.py pre-reduces duplicate ids with
a segment-sum before calling the kernel — the deterministic SPMD analogue of
the paper's "alleviate read/write conflicts in shared memory".  After
pre-reduction the final scatter of the produced rows is conflict-free.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Rows gathered per grid step: 32 row DMAs in flight at once amortize the
# per-step overhead, and (32, K) blocks meet the TPU's 8-row tiling.
ROWS_PER_STEP = 32

# Trace-time launch counter: every Python call of gather_fma_rows binds one
# pallas_call into the traced program, so counting calls during tracing counts
# kernel launches per compiled step.  benchmarks/bench_backends.py uses this to
# verify the single-launch row_update_many contract (groups/step -> 1 launch).
_LAUNCHES = 0


def launch_count() -> int:
    """Number of gather-FMA pallas_call binds since the last reset."""
    return _LAUNCHES


def reset_launch_count() -> None:
    """Zero the trace-time pallas_call launch counter."""
    global _LAUNCHES
    _LAUNCHES = 0


def _gather_into(ids_ref, table_hbm, buf, sems):
    """DMA this grid step's ROWS_PER_STEP rows of the HBM table into ``buf``
    and return them as an fp32 (ROWS_PER_STEP, K) value.

    ``buf`` is (ROWS_PER_STEP, A, K): each DMA copies the A-row block that
    holds its row.  A is 1 for 32-bit tables; the TPU slices packed (int8)
    HBM rows only in 8-row aligned blocks, so there A = 8 and the row is
    picked out of its block in VMEM.  Blocks are clamped to the table, so
    tables whose row count is not a multiple of A still gather in bounds."""
    base = pl.program_id(0) * ROWS_PER_STEP
    rows, align = table_hbm.shape[0], buf.shape[1]
    starts, offsets = [], []
    for r in range(ROWS_PER_STEP):
        i = ids_ref[base + r]
        start = jnp.minimum(i - i % align, rows - align)
        if rows % align == 0:
            start = pl.multiple_of(start, align)
        starts.append(start)
        offsets.append(i - start)
    copies = [pltpu.make_async_copy(table_hbm.at[pl.ds(s, align)],
                                    buf.at[r], sems.at[r])
              for r, s in enumerate(starts)]
    for c in copies:
        c.start()
    for c in copies:
        c.wait()
    if align == 1:
        return buf[...].astype(jnp.float32).reshape(ROWS_PER_STEP,
                                                     buf.shape[2])
    slot = jax.lax.broadcasted_iota(jnp.int32, buf.shape[1:], 0)
    picked = [jnp.sum(jnp.where(slot == off, buf[r].astype(jnp.float32), 0.0),
                      axis=0, keepdims=True)
              for r, off in enumerate(offsets)]
    return jnp.concatenate(picked, axis=0)


def _row_gather_call(kernel, ids, hbm_table, row_inputs, out_dtype,
                     interpret, smem_inputs=()):
    """Grid over ROWS_PER_STEP-row groups of ``ids``: ``hbm_table`` stays in
    HBM and is gathered by row DMA; each of ``row_inputs`` (b, *) is blocked
    alongside the ids, and ``smem_inputs`` (small scalars) sit whole in SMEM.
    Returns the (b, K) output."""
    b = ids.shape[0]
    k = hbm_table.shape[1]
    align = min(1 if hbm_table.dtype.itemsize == 4 else 8,
                hbm_table.shape[0])
    pad = -b % ROWS_PER_STEP          # id 0 fills the last group
    bp = b + pad
    blocked = [pl.BlockSpec((ROWS_PER_STEP, x.shape[1]), lambda g, ids: (g, 0))
               for x in row_inputs]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(pl.cdiv(bp, ROWS_PER_STEP),),
        in_specs=([pl.BlockSpec(memory_space=pl.ANY)] + blocked
                  + [pl.BlockSpec(memory_space=pltpu.SMEM)] * len(smem_inputs)),
        out_specs=pl.BlockSpec((ROWS_PER_STEP, k), lambda g, ids: (g, 0)),
        scratch_shapes=[pltpu.VMEM((ROWS_PER_STEP, align, k),
                                   hbm_table.dtype),
                        pltpu.SemaphoreType.DMA((ROWS_PER_STEP,))],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bp, k), out_dtype),
        interpret=interpret,
    )(jnp.pad(ids.astype(jnp.int32), (0, pad)), hbm_table,
      *[jnp.pad(x, ((0, pad), (0, 0))) for x in row_inputs], *smem_inputs)
    return out[:b]


def _gather_dequant_kernel(ids_ref, q_hbm, scale_ref, out_ref, buf, sems):
    """out[r] = q[ids[r]].astype(f32) * scale[ids[r]] for this step's rows."""
    out_ref[...] = _gather_into(ids_ref, q_hbm, buf, sems) * scale_ref[...]


def gather_dequant_rows(q: jax.Array, scale: jax.Array, ids: jax.Array, *,
                        interpret: bool = False) -> jax.Array:
    """Gather + dequantize int8 rows in-kernel: returns fp32 ``q[ids] *
    scale[ids]`` for ids (B,).

    Same row-DMA structure as :func:`gather_fma_rows`: the int8 rows are
    gathered HBM->VMEM and multiplied by their scales inside the kernel — the
    fp32 table never exists, only the (B, K) gathered block does.  The (B, 1)
    scales are gathered by XLA beforehand (4 bytes a row).  q: (R, K) int8,
    scale: (R, 1) fp32.
    """
    global _LAUNCHES
    _LAUNCHES += 1
    return _row_gather_call(_gather_dequant_kernel, ids, q,
                            [scale[ids].astype(jnp.float32)], jnp.float32,
                            interpret)


def _gather_fma_kernel(ids_ref, table_hbm, grad_ref, lr_ref, out_ref, buf,
                       sems):
    """out[r] = table[ids[r]] - lr * grad[r] for this step's rows."""
    row = _gather_into(ids_ref, table_hbm, buf, sems)
    g = grad_ref[...].astype(jnp.float32)
    out_ref[...] = (row - lr_ref[0, 0] * g).astype(out_ref.dtype)


def gather_fma_rows(table: jax.Array, ids: jax.Array, grads: jax.Array,
                    lr, *, interpret: bool = False):
    """Returns new values for rows ``ids``: table[ids] - lr*grads.

    table: (R, K), ids: (B,) int32 (duplicates allowed — identical outputs
    make the caller's scatter idempotent), grads: (B, K).  Grid over groups
    of ROWS_PER_STEP ids; each step DMAs its table rows, selected by the
    prefetched ids from SMEM.
    """
    global _LAUNCHES
    _LAUNCHES += 1
    lr_arr = jnp.asarray(lr, jnp.float32).reshape(1, 1)
    return _row_gather_call(_gather_fma_kernel, ids, table, [grads],
                            table.dtype, interpret, smem_inputs=[lr_arr])
