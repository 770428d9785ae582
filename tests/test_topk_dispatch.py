"""Top-k dispatch contract (ops/topk.recommend_topk_fused_rows): flat
materialize+top_k for small catalogs / B=1 serving, chunked-scan merge
for big catalogs with batched queries."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from predictionio_tpu.ops.topk import (
    _MIN_BATCH,
    _MIN_ITEMS,
    _SEEN_WIDTHS,
    _trim_seen,
    recommend_topk,
    recommend_topk_chunked,
    recommend_topk_chunked_rows,
    recommend_topk_fused_rows,
    recommend_topk_rows,
)


def _setup(B, I, K=8, S=16, seed=0):
    rng = np.random.default_rng(seed)
    uv = jnp.asarray(rng.standard_normal((B, K)).astype(np.float32))
    itf = jnp.asarray(rng.standard_normal((I, K)).astype(np.float32))
    cols = jnp.asarray(rng.integers(0, I, (B, S)).astype(np.int32))
    mask = jnp.asarray((rng.random((B, S)) < 0.5).astype(np.float32))
    allow = jnp.asarray((rng.random(I) < 0.9).astype(np.float32))
    return uv, itf, cols, mask, allow


def test_fused_matches_flat_small():
    uv, itf, cols, mask, allow = _setup(4, 200)
    fv, fi = recommend_topk_fused_rows(
        uv, np.arange(4, dtype=np.int32), itf, cols, mask, allow, 5)
    rv, ri = recommend_topk(uv, itf, cols, mask, allow, 5)
    np.testing.assert_array_equal(np.asarray(fi), np.asarray(ri))
    np.testing.assert_allclose(np.asarray(fv), np.asarray(rv))


@pytest.mark.parametrize("table_dtype", ["float32", "bfloat16"])
def test_chunked_matches_flat_on_finite_slots(table_dtype):
    uv, itf, cols, mask, allow = _setup(6, 5000, S=24)
    itf = itf.astype(table_dtype)
    fv, fi = recommend_topk(uv, itf, cols, mask, allow, 10)
    cv, ci = recommend_topk_chunked(uv, itf, cols, mask, allow, 10,
                                    chunk=1024)
    fv, fi = np.asarray(fv), np.asarray(fi)
    cv, ci = np.asarray(cv), np.asarray(ci)
    finite = np.isfinite(fv)
    np.testing.assert_array_equal(ci[finite], fi[finite])
    np.testing.assert_allclose(cv[finite], fv[finite], rtol=1e-6)
    assert cv.dtype == fv.dtype == np.float32
    # sentinel slots never collide with real item indices
    assert (ci[~np.isfinite(cv)] >= 5000).all()


def _rows_setup(B, padded, seed=0):
    """A user table, B int32 rows of it and the other arguments as
    ``batch_predict`` hands them over: host arrays, a seen width from
    the menu; ``padded`` repeats row 0 in the batch's second half, as
    ``_pad_batch`` fills a batch up to its menu width."""
    U, I, K, S = 300, 5000, 8, _SEEN_WIDTHS[1]
    rng = np.random.default_rng(seed)
    table = jnp.asarray(rng.standard_normal((U, K)).astype(np.float32))
    uixs = rng.integers(0, U, B).astype(np.int32)
    cols = rng.integers(0, I, (B, S)).astype(np.int32)
    mask = (rng.random((B, S)) < 0.5).astype(np.float32)
    if padded:
        uixs[B // 2:] = uixs[0]
        cols[B // 2:] = 0
        mask[B // 2:] = 0.0
    itf = jnp.asarray(rng.standard_normal((I, K)).astype(np.float32))
    allow = jnp.asarray((rng.random(I) < 0.9).astype(np.float32))
    return table, uixs, itf, cols, mask, allow


@pytest.mark.parametrize("table_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("padded", [False, True], ids=["full", "padded"])
@pytest.mark.parametrize("B", [1, 4, 16, 32])
@pytest.mark.parametrize("form", ["flat", "chunked"])
def test_row_taking_program_equals_vector_program(form, B, padded,
                                                  table_dtype):
    """``recommend_topk*_rows(table, uixs, ...)`` is
    ``recommend_topk*(table[uixs], ...)``: the same indices, and on the
    CPU the same values bit for bit, whichever width the item table
    has."""
    table, uixs, itf, cols, mask, allow = _rows_setup(B, padded, seed=B)
    itf = itf.astype(table_dtype)
    if form == "flat":
        want = recommend_topk(table[uixs], itf, cols, mask, allow, 10)
        got = recommend_topk_rows(table, uixs, itf, cols, mask, allow, 10)
    else:
        want = recommend_topk_chunked(table[uixs], itf, cols, mask, allow,
                                      10, chunk=1024)
        got = recommend_topk_chunked_rows(table, uixs, itf, cols, mask,
                                          allow, 10, chunk=1024)
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))


@pytest.mark.parametrize("B,form", [(4, "flat"), (32, "chunked")])
def test_fused_rows_makes_the_fused_choice(monkeypatch, B, form):
    """The dispatcher picks flat or chunked from the catalog and the
    batch, trims the seen pad on the host for the chunked program, and
    answers what that program answers on ``table[uixs]``."""
    import predictionio_tpu.ops.topk as t

    monkeypatch.setattr(t, "_MIN_ITEMS", 100)
    table, uixs, itf, cols, mask, allow = _rows_setup(B, False, seed=9)
    wide_cols = np.zeros((B, 40), np.int32)
    wide_mask = np.zeros((B, 40), np.float32)
    wide_cols[:, :32], wide_mask[:, :32] = cols, mask
    calls = []
    for name in ("recommend_topk_rows", "recommend_topk_chunked_rows"):
        fn = getattr(t, name)
        monkeypatch.setattr(
            t, name, lambda *a, _fn=fn, _name=name:
            calls.append((_name, a[4].shape)) or _fn(*a))
    got = t.recommend_topk_fused_rows(table, uixs, itf, wide_cols,
                                      wide_mask, allow, 10)
    if form == "flat":
        want = recommend_topk(table[uixs], itf, wide_cols, wide_mask,
                              allow, 10)
    else:
        want = recommend_topk_chunked(
            table[uixs], itf, *_trim_seen(wide_cols, wide_mask), allow, 10)
    assert calls == [("recommend_topk_rows", (B, 40)) if form == "flat"
                     else ("recommend_topk_chunked_rows", (B, 32))]
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    # per-query business rules (2-D allow) stay on the flat program
    calls.clear()
    t.recommend_topk_fused_rows(table, uixs, itf, cols, mask,
                                jnp.ones((B, itf.shape[0]), jnp.float32), 10)
    assert [c[0] for c in calls] == ["recommend_topk_rows"]


@pytest.mark.parametrize("B", [1, 4, 16])
def test_batch_topk_brute_equals_vectors_through_fused(B):
    """``ALSModel.batch_topk`` on the brute branch hands the user
    table, the indices and its bfloat16 serving copy of the item table
    to the dispatcher: what it answers is what the eager gather +
    ``recommend_topk`` answers on that copy (5,000 items: the flat
    side)."""
    from predictionio_tpu.models.als import ALSModel
    from predictionio_tpu.utils.bimap import EntityIdIxMap

    table, uixs, itf, cols, mask, _ = _rows_setup(B, B > 1, seed=40 + B)
    model = ALSModel(
        rank=int(table.shape[1]), user_factors=table, item_factors=itf,
        user_ids=EntityIdIxMap.from_ids(
            [f"u{i}" for i in range(table.shape[0])]),
        item_ids=EntityIdIxMap.from_ids(
            [f"i{i}" for i in range(itf.shape[0])]),
        seen_by_user={})
    got = model.batch_topk(uixs, cols, mask, None, 10)
    assert model.serving_item_factors().dtype == jnp.bfloat16
    want = recommend_topk(
        table[uixs], itf.astype(jnp.bfloat16), cols, mask,
        jnp.ones((itf.shape[0],), jnp.float32), 10)
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))


def test_trim_seen_picks_menu_width():
    # host arrays trim to the smallest covering menu width...
    cols = np.zeros((3, 513), np.int32)
    mask = np.zeros((3, 513), np.float32)
    mask[1, 30] = 1.0
    tc, tm = _trim_seen(cols, mask)
    assert tm.shape[1] == 32 and tm.shape[1] in _SEEN_WIDTHS
    # ...a menu-width input skips the scan entirely...
    c512 = np.zeros((3, 512), np.int32)
    m512 = np.zeros((3, 512), np.float32)
    tc, tm = _trim_seen(c512, m512)
    assert tm.shape[1] == 512 and tm is m512
    # ...and device arrays / tracers pass through untouched (no host
    # round-trip, static shapes under jit)
    dc, dm = jnp.asarray(cols), jnp.asarray(mask)
    tc, tm = _trim_seen(dc, dm)
    assert tm is dm

    @jax.jit
    def f(c, m):
        tc, tm = _trim_seen(c, m)
        return tm.shape[1]
    assert f(dc, dm) == 513


def test_dispatch_threshold_uses_chunked(monkeypatch):
    """Above the envelope the dispatcher must route to the chunked
    program (checked by stubbing, not by allocating 1M items)."""
    import predictionio_tpu.ops.topk as t

    calls = []
    monkeypatch.setattr(
        t, "recommend_topk_chunked_rows",
        lambda *a: calls.append("chunked") or t.recommend_topk_rows(*a),
    )
    monkeypatch.setattr(t, "_MIN_ITEMS", 100)
    monkeypatch.setattr(t, "_MIN_BATCH", 2)
    uv, itf, cols, mask, allow = _setup(4, 200)
    uixs = np.arange(4, dtype=np.int32)
    t.recommend_topk_fused_rows(uv, uixs, itf, cols, mask, allow, 5)
    assert calls == ["chunked"]
    # 2-D allow (per-query business rules) must stay on the flat path
    calls.clear()
    allow2 = jnp.ones((4, 200), jnp.float32)
    t.recommend_topk_fused_rows(uv, uixs, itf, cols, mask, allow2, 5)
    assert calls == []


class TestShardedTopk:
    """recommend_topk_sharded — the eval hot path on a mesh (per-shard
    top-k + all-gather candidate merge; Engine.scala:783-799 analogue)."""

    def test_matches_single_device(self, mesh8):
        from predictionio_tpu.ops.topk import recommend_topk_sharded

        B, I, k = 8, 64, 5
        uv, itf, cols, mask, allow = _setup(B, I)
        v_sh, i_sh = recommend_topk_sharded(uv, itf, cols, mask, allow,
                                            k, mesh8)
        v_1, i_1 = recommend_topk(uv, itf, cols, mask, allow, k)
        np.testing.assert_allclose(np.asarray(v_sh), np.asarray(v_1),
                                   rtol=1e-6, atol=1e-6)
        finite = np.isfinite(np.asarray(v_1))
        np.testing.assert_array_equal(np.asarray(i_sh)[finite],
                                      np.asarray(i_1)[finite])

    def test_seen_items_excluded_across_shards(self, mesh8):
        """Seen items on EVERY model shard must be masked — the scatter
        runs in shard-local coordinates."""
        from predictionio_tpu.ops.topk import recommend_topk_sharded

        B, I, k = 8, 64, 10
        uv, itf, cols, mask, _ = _setup(B, I, seed=3)
        mask = jnp.ones_like(mask)          # every listed item is seen
        allow = jnp.ones((I,), jnp.float32)
        _, idx = recommend_topk_sharded(uv, itf, cols, mask, allow, k, mesh8)
        idx, cols = np.asarray(idx), np.asarray(cols)
        for b in range(B):
            assert not set(idx[b]) & set(cols[b]), b

    def test_indivisible_catalog_rejected(self, mesh8):
        from predictionio_tpu.ops.topk import recommend_topk_sharded

        uv, itf, cols, mask, allow = _setup(8, 63)
        with pytest.raises(ValueError, match="divide the model axis"):
            recommend_topk_sharded(uv, itf, cols, mask, allow, 5, mesh8)
