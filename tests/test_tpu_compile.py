"""The main path's Pallas kernels compile for a TPU v5e at real widths.

No chip is needed: the TPU compiler shipped with jaxlib compiles for a
described ``v5e:2x2`` topology.  Interpret mode (tests/test_kernels.py)
cannot show what the chip's compiler refuses — unaligned block shapes,
dot layouts Mosaic does not lower, VMEM overflow — and these compiles do.
Shapes are the ``MF_100M`` smoke shape: batch 1024, 64 negatives, K=128,
400k table rows.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every worker of a
parallel run imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ccl_similarity as ccl
from repro.kernels import embedding_update as emb

B, N, K, R = 1024, 64, 128, 400_000


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
}


@pytest.mark.parametrize("name", list(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = CASES[name]
    assert "tpu_custom_call" in _compiled_text(fn, one_chip, *shapes)
