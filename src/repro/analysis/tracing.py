"""The program's own trace points, named in one place.

Device scopes are ``jax.named_scope`` names: they set the HLO ``op_name``
metadata of every operation traced under them (``heat.*`` for the HEAT
training step, ``topk.*`` for the full-catalog top-k call), cost nothing at
run time, and reach the profiler's device trace through each op's
``tf_op`` stat.  Host spans are :func:`span`\\ s on the profiler's clock
around host work between device calls (``train.*`` for the window loop,
``serve.*`` for the batching server's worker).  Scope names are flat: no
scope is opened inside another.
"""
from __future__ import annotations

import jax

# -- device scopes (core/mf.py) ----------------------------------------------
HEAT_BATCH = "heat.batch"              # the batch law's id work, in-scan
HEAT_GATHER = "heat.gather"            # user, positive and history row gathers
HEAT_SAMPLE = "heat.sample"            # the negative sampler's draw and gather
HEAT_CCL = "heat.ccl"                  # loss forward and backward
HEAT_ROW_UPDATE = "heat.row_update"    # user/item row updates, slot reduction
HEAT_TILE = "heat.tile"                # tile write-through and refresh
TOPK_PREPARE = "topk.prepare"          # user gather and dequantize
TOPK_SCAN = "topk.scan"                # scoring and the running top-k

HEAT_SCOPES = (HEAT_BATCH, HEAT_GATHER, HEAT_SAMPLE, HEAT_CCL,
               HEAT_ROW_UPDATE, HEAT_TILE)
TOPK_SCOPES = (TOPK_PREPARE, TOPK_SCAN)

# -- host spans ----------------------------------------------------------------
TRAIN_DISPATCH = "train.dispatch"      # EpochExecutor.run: start up, enqueue
TRAIN_READBACK = "train.readback"      # run_window: the window's loss readback
SERVE_COLLECT = "serve.collect"        # first request taken to batch close
SERVE_DISPATCH = "serve.dispatch"      # padding, host-to-device copy, the call
SERVE_READBACK = "serve.readback"      # block_until_ready and the host copy
SERVE_FANOUT = "serve.fanout"          # answers, wake-ups, queue-wait counter

HOST_SPANS = (TRAIN_DISPATCH, TRAIN_READBACK, SERVE_COLLECT, SERVE_DISPATCH,
              SERVE_READBACK, SERVE_FANOUT)


def span(name: str):
    """A host span named ``name`` on the profiler's clock; about 0.1 us
    while no profiler runs."""
    return jax.profiler.TraceAnnotation(name)
