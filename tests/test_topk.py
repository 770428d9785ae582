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


# -- the flat path's exact two-stage selection --------------------------------

from predictionio_tpu.ops import topk as topk_ops

#: (k, catalog): each k with a catalog that is whole groups of the
#: rule's width and one that leaves a tail short of a group
_TWO_STAGE_SHAPES = [(10, 12_800), (10, 12_877), (32, 45_056), (32, 45_100),
                     (100, 110_080), (100, 110_000)]


def _score_kinds(B, I, width, rng):
    """The score matrices the selection has to get right."""
    plain = rng.standard_normal((B, I)).astype(np.float32)
    ties = rng.integers(0, 4, (B, I)).astype(np.float32)
    one_group = plain.copy()
    one_group[:, 3 * width:4 * width] += 100.0
    few = np.full((B, I), -np.inf, np.float32)
    few[:, rng.integers(0, I, 7)] = rng.standard_normal(7).astype(np.float32)
    tail_wins = plain.copy()
    tail_wins[:, -5:] += 100.0
    return {"plain": plain, "ties": ties, "one_group": one_group,
            "fewer_than_k": few, "all_equal": np.zeros((B, I), np.float32),
            "tail_wins": tail_wins}


def _assert_same_on_finite_slots(got, want):
    gv, gi = (np.asarray(a) for a in got)
    wv, wi = (np.asarray(a) for a in want)
    np.testing.assert_array_equal(gv, wv)
    finite = np.isfinite(wv)
    np.testing.assert_array_equal(gi[finite], wi[finite])


@pytest.mark.parametrize("k,I", _TWO_STAGE_SHAPES)
@pytest.mark.parametrize("B", [1, 2, 3, 4, 8, 16])
def test_two_stage_selection_equals_lax_top_k(B, k, I):
    """Values and indices of the two-stage selection equal
    ``lax.top_k``'s on every finite slot, ties included."""
    width = topk_ops.two_stage_group_width(I, k)
    assert width and width % 128 == 0
    assert (I % width == 0) == (I in (12_800, 45_056, 110_080))
    whole = I // width * width
    lanes = 8 if B % 8 == 0 else B
    two_stage = jax.jit(lambda s: topk_ops._two_stage_topk(
        s[:, :whole].reshape(B // lanes, lanes, whole // width, width),
        s[:, whole:], k))
    single = jax.jit(lambda s: jax.lax.top_k(s, k))
    rng = np.random.default_rng(B * 1000 + k)
    for kind, scores in _score_kinds(B, I, width, rng).items():
        try:
            _assert_same_on_finite_slots(two_stage(scores), single(scores))
        except AssertionError as e:
            raise AssertionError(f"{kind}: {e}") from e


@pytest.mark.parametrize("table_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("allow_rank", [1, 2])
@pytest.mark.parametrize("S", [8, 128])
@pytest.mark.parametrize("B", [1, 2, 4, 8, 16])
@pytest.mark.parametrize("I", [12_800, 12_877], ids=["whole_groups", "tail"])
def test_recommend_topk_two_stage_equals_single_top_k(I, B, S, allow_rank,
                                                      table_dtype):
    """``recommend_topk`` above the rule's threshold against the same
    masked scores under one ``lax.top_k``: a catalog of whole groups
    and one with a tail (some of its items seen), seen widths 8 and
    128, a shared and a per-query ``allow``, integer factors so that
    the matmuls agree to the bit and ties abound; over a float32 table
    and over the bfloat16 one the model serves from (the scores float32
    either way)."""
    K, k = 8, 10
    assert topk_ops.two_stage_group_width(I, k) == 128
    rng = np.random.default_rng(S + B + allow_rank)
    uv = jnp.asarray(rng.integers(-2, 3, (B, K)).astype(np.float32))
    itf = jnp.asarray(rng.integers(-2, 3, (I, K)).astype(np.float32)
                      ).astype(table_dtype)
    cols = rng.integers(0, I, (B, S)).astype(np.int32)
    cols[:, :3] = I - 1 - np.arange(3)            # the last items: the tail's
    mask = (rng.random((B, S)) < 0.7).astype(np.float32)
    mask[:, :2] = 1.0
    cols, mask = jnp.asarray(cols), jnp.asarray(mask)
    shape = (I,) if allow_rank == 1 else (B, I)
    allow = jnp.asarray((rng.random(shape) < 0.8).astype(np.float32))
    want = jax.jit(lambda *a: jax.lax.top_k(topk_ops._masked_scores(*a), k))(
        uv, itf, cols, mask, allow)
    got = recommend_topk(uv, itf, cols, mask, allow, k)
    assert got[0].shape == got[1].shape == (B, k)
    assert got[0].dtype == want[0].dtype == jnp.float32
    _assert_same_on_finite_slots(got, want)
    seen = {(b, int(c)) for b in range(B) for c, m in
            zip(np.asarray(cols)[b], np.asarray(mask)[b]) if m > 0}
    gv, gi = np.asarray(got[0]), np.asarray(got[1])
    assert not any((b, int(i)) in seen
                   for b in range(B) for v, i in zip(gv[b], gi[b])
                   if np.isfinite(v))


def test_two_stage_rule_is_a_function_of_the_shapes():
    """Tiny catalogs, an empty one and k above 100 keep the single
    ``lax.top_k``; the Books catalog at ``num`` 10 takes 640-column
    groups, whole or with a tail."""
    width = topk_ops.two_stage_group_width
    assert [width(i, min(k, i)) for i in (0, 17, 50, 5_000)
            for k in (10, 32, 100, 1000)] == [0] * 16
    assert width(100_000, 1000) == 0 and width(786_432, 1000) == 0
    assert width(4_400_000, 320) == 0 and width(4_400_000, 1000) == 0
    assert width(4_400_000, 10) == width(4_400_123, 10) == 640
    empty = recommend_topk(
        jnp.zeros((2, 8)), jnp.zeros((0, 8)), jnp.zeros((2, 8), jnp.int32),
        jnp.zeros((2, 8)), jnp.zeros((0,)), 10)
    assert empty[0].shape == empty[1].shape == (2, 0)
    for items in (12_800, 100_000, 4_400_000):
        for k in (10, 32, 100):
            w = width(items, k)
            assert w == 0 or (w % 128 == 0 and items // w >= 8 * k)
    # the counter's rule is the program's: chunked dispatches and
    # catalogs under the threshold do not count
    itf = jax.ShapeDtypeStruct((4_400_000, 128), jnp.float32)
    allow = jax.ShapeDtypeStruct((4_400_000,), jnp.float32)
    assert topk_ops.selects_two_stage(allow, itf, 8, 10)
    assert not topk_ops.selects_two_stage(allow, itf, 32, 10)    # chunked
    small = jax.ShapeDtypeStruct((5_000, 8), jnp.float32)
    assert not topk_ops.selects_two_stage(
        jax.ShapeDtypeStruct((5_000,), jnp.float32), small, 8, 10)


# -- the table's width: bfloat16 is inside the guarantee, int8 is not ----------

def _int8_rows(table):
    """``table`` through int8 with one scale a row, back in float32."""
    scale = np.abs(table).max(axis=1, keepdims=True) / 127.0
    return (np.round(table / scale) * scale).astype(np.float32)


@pytest.mark.parametrize("form", ["flat", "chunked"])
@pytest.mark.parametrize("B", [1, 2, 8, 16])
def test_bfloat16_table_holds_the_tie_bound_and_int8_does_not(B, form):
    """Sixteen users' answers, ``B`` a program, checked as the ALS
    cells check theirs (``als_numpy.check_answer`` against float32
    ``u . V``: ids may differ only where scores tie within
    2^-8 |u| |v|). From the float32 table and from its bfloat16 copy
    every answer passes. From a table quantised to int8 with one scale
    a row many do not: the factors have one dominant component (a
    popularity direction, which the users here do not weigh), so the
    row's scale is set by it and the other components keep two or
    three bits. On factors without one int8 passes this bound too: it
    is Cauchy-Schwarz's worst case (PERF.md §7, PR 35)."""
    from benchmarks.reference import als_numpy

    I, K, users, k = 12_800, 32, 16, 10
    rng = np.random.default_rng(35)
    itf = rng.standard_normal((I, K)).astype(np.float32)
    itf[:, 0] = 30.0 + rng.standard_normal(I)
    uf = rng.standard_normal((users, K)).astype(np.float32)
    uf[:, 0] = 0.0
    cols = rng.integers(0, I, (users, 8)).astype(np.int32)
    mask = np.ones((users, 8), np.float32)
    allow = jnp.ones((I,), jnp.float32)
    reference = als_numpy.reference_scores(itf, uf)
    norms = als_numpy.item_norms(itf)

    def program(*a):
        if form == "flat":
            return recommend_topk(*a, allow, k)
        return recommend_topk_chunked(*a, allow, k, chunk=2048)

    def failed(table):
        n = 0
        for lo in range(0, users, B):
            part = slice(lo, lo + B)
            vals, idxs = (np.asarray(a) for a in program(
                jnp.asarray(uf[part]), table, jnp.asarray(cols[part]),
                jnp.asarray(mask[part])))
            assert vals.dtype == np.float32
            for j, u in enumerate(range(lo, lo + B)):
                n += als_numpy.check_answer(
                    reference[u], float(np.linalg.norm(uf[u])), norms,
                    cols[u], list(zip(idxs[j].tolist(), vals[j].tolist())),
                    k) is not None
        return n

    assert failed(jnp.asarray(itf)) == 0
    assert failed(jnp.asarray(itf).astype(jnp.bfloat16)) == 0
    assert failed(jnp.asarray(_int8_rows(itf))) >= users // 4


def _served_counts(n_items, n_queries):
    """(dispatches, two-stage dispatches, /metrics text) after
    ``n_queries`` known-user queries against a seeded model of
    ``n_items`` items behind a batching engine server."""
    import datetime as dt
    import json
    import urllib.request

    from predictionio_tpu.api.engine_server import EngineServer
    from predictionio_tpu.controller.base import FirstServing
    from predictionio_tpu.models.als import ALSModel
    from predictionio_tpu.storage.base import EngineInstance
    from predictionio_tpu.templates import recommendation as rec
    from predictionio_tpu.utils.bimap import EntityIdIxMap
    from predictionio_tpu.workflow.deploy import DeployedEngine, ServerConfig

    rng = np.random.default_rng(n_items)
    model = ALSModel(
        rank=8,
        user_factors=jnp.asarray(
            rng.standard_normal((12, 8)).astype(np.float32)),
        item_factors=jnp.asarray(
            rng.standard_normal((n_items, 8)).astype(np.float32)),
        user_ids=EntityIdIxMap.from_ids([f"u{i}" for i in range(12)]),
        item_ids=EntityIdIxMap.from_ids([f"i{i}" for i in range(n_items)]),
        seen_by_user={0: np.asarray([1, 2, 3], np.int32)})
    algo = rec.ALSAlgorithm(rec.ALSAlgorithmParams(rank=8, use_mesh=False))
    now = dt.datetime.now(dt.timezone.utc)
    instance = EngineInstance(
        id="t", status="COMPLETED", start_time=now, completion_time=now,
        engine_id="t", engine_version="1", engine_variant="t",
        engine_factory="t")
    server = EngineServer(
        DeployedEngine(None, instance, [algo], FirstServing(), [model]),
        ServerConfig(ip="127.0.0.1", port=0, batching=True))
    server.start()
    try:
        base = f"http://127.0.0.1:{server.port}"
        for q in range(n_queries):
            req = urllib.request.Request(
                f"{base}/queries.json",
                data=json.dumps({"user": f"u{q}", "num": 10}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=60) as resp:
                scores = json.loads(resp.read())["itemScores"]
            assert len(scores) == 10
            assert q or not {"i1", "i2", "i3"} & {s["item"] for s in scores}
        with urllib.request.urlopen(f"{base}/stats.json") as resp:
            serving = json.loads(resp.read())["serving"]
        with urllib.request.urlopen(f"{base}/metrics") as resp:
            metrics = resp.read().decode()
    finally:
        server.stop()
    # the served table is the model's bfloat16 copy, and the server says so
    assert serving["scoreTableBytesPerEntry"] == 2
    assert "pio_serving_score_table_bytes_per_entry 2" in metrics
    assert model.item_factors.dtype == jnp.float32
    return serving["dispatches"], serving["topkTwoStageDispatches"], metrics


def test_two_stage_counter_equals_dispatches_above_the_rule():
    """A catalog the rule selects in two stages (12,800 items at
    ``num`` 10, as Books' 4.4M is): every dispatch of the batching
    server counts, on ``/stats.json`` and ``/metrics``."""
    dispatches, two_stage, metrics = _served_counts(12_800, 3)
    assert dispatches == 3 and two_stage == 3
    assert "pio_serving_topk_two_stage_dispatches_total 3" in metrics


def test_two_stage_counter_stays_zero_under_the_rule():
    dispatches, two_stage, metrics = _served_counts(500, 2)
    assert dispatches == 2 and two_stage == 0
    assert "pio_serving_topk_two_stage_dispatches_total 0" in metrics
