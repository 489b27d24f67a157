"""The main path's Pallas kernels compile for a TPU v5e at real widths.

No chip is needed: the TPU compiler shipped with jaxlib compiles for a
described ``v5e:2x2`` topology.  Interpret mode (tests/test_kernels.py)
cannot show what the chip's compiler refuses — unaligned block shapes,
dot layouts Mosaic does not lower, VMEM overflow — and these compiles do.
Shapes are the ``MF_100M`` smoke shape: batch 1024, 64 negatives, K=128,
400k table rows; the top-k scan at the serving call's 32 users and
512-item chunks.  One whole HEAT training window is compiled at that shape
too, to show that the device scopes of ``repro.analysis.tracing`` survive
the chip compiler's fusion.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every worker of a
parallel run imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ccl_similarity as ccl
from repro.kernels import embedding_update as emb
from repro.kernels import topk_scan as ts

B, N, K, R = 1024, 64, 128, 400_000
SERVE_B, ITEM_CHUNK = 32, 512            # BatchingRecommender's serving call


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A single described v5e device, with the persistent compilation cache
    off (entries compiled for a described chip cannot be read back)."""
    from jax.experimental.compilation_cache import compilation_cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


F32, I32 = jnp.float32, jnp.int32
_VEC, _NEG, _COL, _ROW = ((B, K), F32), ((B, N, K), F32), ((B, 1), F32), \
    ((B, N), F32)

CASES = {
    "ccl_stats": (lambda u, p, n: ccl.ccl_stats_pallas(u, p, n),
                  [_VEC, _VEC, _NEG]),
    "ccl_bwd": (lambda u, p, n, uu, pp, up, nn, un, g: ccl.ccl_bwd_pallas(
        u, p, n, uu, pp, up, nn, un, g, mu=1.0, theta=0.0),
        [_VEC, _VEC, _NEG, _COL, _COL, _COL, _ROW, _ROW, ((), F32)]),
    "ccl_stats_shared": (lambda u, p, n: ccl.ccl_stats_shared_pallas(u, p, n),
                         [_VEC, _VEC, ((N, K), F32)]),
    "ccl_bwd_shared": (
        lambda u, p, n, uu, pp, up, nn, un, w, g: ccl.ccl_bwd_shared_pallas(
            u, p, n, uu, pp, up, nn, un, w, g, mu=1.0, theta=0.0),
        [_VEC, _VEC, ((N, K), F32), _COL, _COL, _COL, ((1, N), F32), _ROW,
         _COL, ((), F32)]),
    # one step's item update: B positives + B slot-reduced negatives
    "gather_fma_rows": (lambda t, i, g: emb.gather_fma_rows(t, i, g, 0.05),
                        [((R, K), F32), ((2 * B,), I32), ((2 * B, K), F32)]),
    "gather_dequant_rows": (
        lambda q, s, i: emb.gather_dequant_rows(q, s, i),
        [((R, K), jnp.int8), ((R, 1), F32), ((B,), I32)]),
    # the serving call's full-catalog top-10 over int8 and fp32 items
    "topk_scan_int8": (
        lambda u, q, s: ts.topk_scan_pallas(
            u, q, s, None, k=10, similarity="cosine", item_chunk=ITEM_CHUNK),
        [((SERVE_B, K), F32), ((R, K), jnp.int8), ((R, 1), F32)]),
    "topk_scan_fp32": (
        lambda u, t: ts.topk_scan_pallas(
            u, t, None, None, k=10, similarity="cosine",
            item_chunk=ITEM_CHUNK),
        [((SERVE_B, K), F32), ((R, K), F32)]),
}


@pytest.mark.parametrize("name", list(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = CASES[name]
    assert "tpu_custom_call" in _compiled_text(fn, one_chip, *shapes)


def _executed(hlo_text: str) -> list:
    """(opcode, shape, custom-call target, op_name) of every instruction the
    device
    runs as an op: those of the entry computation and of the while bodies,
    conditions and conditional branches it reaches (not fused computations,
    reducers or comparators)."""
    comps, cur, entry = {}, None, None
    for line in hlo_text.splitlines():
        if line and not line.startswith(" ") and line.rstrip().endswith("{"):
            cur = line.split()[1 if line.startswith("ENTRY") else 0]
            entry = cur if line.startswith("ENTRY") else entry
            comps[cur] = []
        elif cur and line.startswith("  "):
            comps[cur].append(line)
    seen, todo, out = set(), [entry], []
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        for line in comps[name]:
            todo += re.findall(r"(?:body|condition|true_computation|"
                               r"false_computation)=(%[\w.\-]+)", line)
            for group in re.findall(r"branch_computations=\{([^}]*)\}",
                                    line):
                todo += [c.strip() for c in group.split(",")]
            op = re.search(r"=\s*(.+?)\s([a-z][a-z\-]*)\(", line)
            if op:
                target = re.search(r'custom_call_target="([^"]*)"', line)
                name_m = re.search(r'op_name="([^"]*)"', line)
                out.append((op.group(2), op.group(1),
                            target.group(1) if target else "",
                            name_m.group(1) if name_m else ""))
    return out


#: Instructions that may carry no scope: loop control (the scan's own ops,
#: named for the scan and not for its body function, and scalar counters
#: and conditions), parameters, tuples, copies, constants and buffer
#: allocation.
_STRUCTURAL = {"parameter", "get-tuple-element", "tuple", "constant",
               "bitcast", "copy", "copy-start", "copy-done", "iota"}
_LOOP = re.compile(r"jit\(run_window\)(/while(/body|/cond)?)?/[a-z_]+")


def test_heat_window_keeps_its_scopes_on_v5e(one_chip):
    """One ``EpochExecutor`` window of the HEAT step at K=128, n=64,
    B=1024 over 400k-row tables: every device scope survives fusion on the
    chip's compiler, and what runs under none is loop control, parameters,
    tuples and copies."""
    from repro.analysis import tracing
    from repro.core import mf
    from repro.data import pipeline
    from repro.train import trainer

    cfg = mf.MFConfig(num_users=R, num_items=R, emb_dim=K, num_negatives=N,
                      tile_size=B, refresh_interval=4096)
    train_pos = (jnp.arange(R * 4, dtype=I32) * 7919 % R).reshape(R, 4)
    dds = pipeline.DeviceCFDataset(R, R, train_pos, None)
    ex = trainer.EpochExecutor(mf.make_scan_body(
        cfg, lambda s: pipeline.cf_batch_device(dds, 0, s, B), 0), 4)
    shapes = jax.eval_shape(lambda: mf.init_mf(jax.random.PRNGKey(0), cfg))
    state = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=one_chip), shapes)
    start = jax.ShapeDtypeStruct((), I32, sharding=one_chip)
    ops = _executed(ex._compiled(4).lower(state, start).compile().as_text())

    def scope(op_name):
        found = re.findall(r"(?:heat|topk)\.[a-z_]+", op_name)
        return found[-1] if found else None

    fused = {scope(n) for op, _, _, n in ops if op == "fusion"}
    assert set(tracing.HEAT_SCOPES) <= fused
    bare = [(op, shape, n) for op, shape, target, n in ops
            if scope(n) is None and op not in _STRUCTURAL
            and target != "AllocateBuffer" and not _LOOP.fullmatch(n)
            and not re.match(r"\w+\[\]", shape)]
    assert bare == []
