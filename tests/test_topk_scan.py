"""The top-k scan kernel (kernels/topk_scan.py, interpret mode) against its
oracle, the XLA chunk loop ``ref.topk_scan_ref``: equal ids over fp32 and
int8 tables, cosine and dot, with and without an exclusion mask, ragged
catalogs, and a merged-chunk count equal to a numpy count of the chunks
that hold a new running top-k entry."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref
from repro.kernels.topk_scan import ROW_BLOCK, chunk_width
from repro.optim import quantization as qz

DIM = 16

# (table format, similarity, masked share, B, I, k, item_chunk)
CASES = {
    "fp32-cosine": ("fp32", "cosine", 0.0, 5, 1000, 10, 200),
    "fp32-dot": ("fp32", "dot", 0.0, 5, 1000, 10, 256),
    "int8-cosine": ("int8", "cosine", 0.0, 5, 1000, 10, 200),
    "int8-dot": ("int8", "dot", 0.0, 5, 1000, 10, 256),
    "fp32-cosine-mask": ("fp32", "cosine", 0.3, 5, 1000, 10, 200),
    "int8-dot-mask": ("int8", "dot", 0.3, 5, 1000, 10, 256),
    # most of every chunk masked: fewer survivors per chunk than k
    "int8-cosine-k-over-survivors": ("int8", "cosine", 0.9, 3, 1500, 20, 128),
    # k = I (the clamp): every item ranked, masked ones last at -inf
    "fp32-cosine-k-is-catalog": ("fp32", "cosine", 0.2, 2, 300, 300, 128),
    # one row over 47 chunks: most chunks are scored and not merged
    "int8-cosine-b1": ("int8", "cosine", 0.0, 1, 6000, 5, 128),
    "int8-cosine-b32": ("int8", "cosine", 0.0, 32, 5000, 10, 512),
    # three blocks of user rows, each merging on its own
    "int8-cosine-mask-b300": ("int8", "cosine", 0.2, 300, 2000, 10, 256),
    # a catalog smaller than one 128-lane chunk
    "fp32-dot-tiny-catalog": ("fp32", "dot", 0.0, 4, 90, 20, 64),
}


def _scores(u, items, scale, similarity):
    """float64 (B, I) scores as the kernel orders them."""
    it = items.astype(np.float64)
    if scale is not None:
        it = it * scale
    s = u.astype(np.float64) @ it.T
    if similarity == "cosine":
        s = s / np.maximum(np.linalg.norm(it, axis=1), 1e-12)[None, :]
    return s


def _merged_chunks(s, k, chunk):
    """(Block of user rows, chunk) pairs in which some row's score beats
    that row's running k-th."""
    count = 0
    for rows in np.split(s, range(ROW_BLOCK, s.shape[0], ROW_BLOCK)):
        for c0 in range(0, s.shape[1], chunk):
            kth = (np.sort(rows[:, :c0], axis=1)[:, -k] if c0 >= k
                   else np.full(rows.shape[0], -np.inf))
            count += bool(np.any(rows[:, c0:c0 + chunk] > kth[:, None]))
    return count


@pytest.mark.parametrize("case", list(CASES))
def test_topk_scan_matches_oracle(case):
    fmt, similarity, masked, b, num_items, k, item_chunk = CASES[case]
    r = np.random.default_rng(len(case))
    u = r.normal(size=(b, DIM)).astype(np.float32)
    table = r.normal(size=(num_items, DIM)).astype(np.float32)
    if fmt == "int8":
        qt = qz.quantize_table(jnp.asarray(table))
        items, scale = qt.q, qt.scale
    else:
        items, scale = jnp.asarray(table), None
    mask = r.random((b, num_items)) < masked if masked else None
    excl = None if mask is None else jnp.asarray(mask)

    got, merged = ops.topk_scan(jnp.asarray(u), items, scale, k,
                                similarity=similarity,
                                item_chunk=item_chunk, exclude_mask=excl)
    chunk = chunk_width(item_chunk)
    want = ref.topk_scan_ref(jnp.asarray(u), items, scale, k,
                             similarity=similarity, item_chunk=chunk,
                             exclude_mask=excl)
    assert got.shape == (b, k)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    s = _scores(u, np.asarray(items),
                None if scale is None else np.asarray(scale), similarity)
    if mask is not None:
        s = np.where(mask, -np.inf, s)
    assert int(merged) == _merged_chunks(s, k, chunk)
