"""The k-clamp contract of the serving top-k paths (ops/topk).

``jax.lax.top_k`` asserts when ``k`` exceeds the candidate column
count. Every serving top-k clamps instead: a tiny catalog, or an ANN
shortlist smaller than the requested width after seen-item masking,
returns the columns that exist — fewer results, never an XLA error.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from predictionio_tpu.ops.topk import (
    recommend_topk,
    recommend_topk_chunked,
    recommend_topk_fused_rows,
    similar_topk,
    topk_scores,
)


def _setup(B, I, K=8, S=4, seed=0):
    rng = np.random.default_rng(seed)
    uv = jnp.asarray(rng.standard_normal((B, K)).astype(np.float32))
    itf = jnp.asarray(rng.standard_normal((I, K)).astype(np.float32))
    cols = jnp.asarray(rng.integers(0, I, (B, S)).astype(np.int32))
    mask = jnp.asarray((rng.random((B, S)) < 0.5).astype(np.float32))
    allow = jnp.ones((I,), dtype=jnp.float32)
    return uv, itf, cols, mask, allow


def test_topk_scores_clamps_k_to_columns():
    scores = jnp.asarray(np.arange(12, dtype=np.float32).reshape(2, 6))
    vals, idxs = topk_scores(scores, 50)
    assert vals.shape == (2, 6)
    np.testing.assert_array_equal(np.asarray(idxs[0]), [5, 4, 3, 2, 1, 0])


def test_recommend_topk_clamps_k_to_catalog():
    uv, itf, cols, mask, allow = _setup(3, 7)
    vals, idxs = recommend_topk(uv, itf, cols, mask, allow, 32)
    assert vals.shape == (3, 7) and idxs.shape == (3, 7)
    # clamped result ranks exactly like a legal k over the same scores
    ev, ei = recommend_topk(uv, itf, cols, mask, allow, 7)
    np.testing.assert_array_equal(np.asarray(idxs), np.asarray(ei))


def test_chunked_clamps_k_on_both_dispatch_arms():
    # small catalog takes the flat arm; chunk smaller than the catalog
    # forces the scan arm — both clamp to I
    uv, itf, cols, mask, allow = _setup(2, 9)
    for chunk in (64, 4):
        vals, idxs = recommend_topk_chunked(uv, itf, cols, mask, allow,
                                            99, chunk=chunk)
        assert vals.shape == (2, 9)


def test_fused_dispatcher_clamps_k():
    uv, itf, cols, mask, allow = _setup(2, 5)
    vals, idxs = recommend_topk_fused_rows(
        uv, np.arange(2, dtype=np.int32), itf, np.asarray(cols),
        np.asarray(mask), allow, 40)
    assert vals.shape == (2, 5)


def test_similar_topk_clamps_k_to_catalog():
    uv, itf, cols, mask, allow = _setup(2, 6, S=2)
    vals, idxs = similar_topk(itf[:2], itf, cols, mask, allow, 100)
    assert vals.shape == (2, 6)


def test_tiny_catalog_masked_rows_still_return():
    # every candidate masked: all -inf values, shape intact (callers
    # already skip non-finite slots)
    uv, itf, cols, mask, _ = _setup(2, 3)
    deny = jnp.zeros((3,), dtype=jnp.float32)
    vals, idxs = recommend_topk(uv, itf, cols, mask, deny, 8)
    assert vals.shape == (2, 3)
    assert not np.isfinite(np.asarray(vals)).any()
