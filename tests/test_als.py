"""ALS kernel correctness: bucketing, explicit/implicit solves vs a NumPy
reference, sharded execution, top-k masking, model persistence.

Mirrors the role of MLlib's ALSSuite for the reference templates (the
reference itself has no in-tree ALS tests — the kernels were external;
here they are in-tree so they get in-tree tests, SURVEY.md §2 note)."""

import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.ops import als as als_mod
from predictionio_tpu.ops.als import (
    ALSFactors,
    RatingsCOO,
    als_train,
    bucket_rows,
    chunk_rows,
    half_step_flops,
    predict_ratings,
    rmse,
    solve_half,
)


def _random_coo(rng, users=30, items=20, density=0.3):
    mask = rng.random((users, items)) < density
    rows, cols = np.nonzero(mask)
    vals = rng.uniform(1.0, 5.0, size=len(rows)).astype(np.float32)
    return RatingsCOO(
        rows.astype(np.int32), cols.astype(np.int32), vals, users, items
    )


def _numpy_solve_half(V, coo, lam, implicit=False, alpha=40.0):
    """Direct per-row normal-equation solve, the correctness oracle."""
    K = V.shape[1]
    out = np.zeros((coo.num_rows, K), dtype=np.float64)
    Vd = np.asarray(V, dtype=np.float64)
    gram = Vd.T @ Vd
    for u in range(coo.num_rows):
        sel = coo.rows == u
        if not sel.any():
            continue
        idx = coo.cols[sel]
        r = coo.vals[sel].astype(np.float64)
        F = Vd[idx]
        if implicit:
            w = alpha * r
            A = gram + (F * w[:, None]).T @ F + lam * np.eye(K)
            b = ((1.0 + w)[:, None] * F).sum(axis=0)
        else:
            A = F.T @ F + lam * len(r) * np.eye(K)
            b = (r[:, None] * F).sum(axis=0)
        out[u] = np.linalg.solve(A, b)
    return out


class TestBucketing:
    def test_bucket_shapes_and_content(self):
        rng = np.random.default_rng(0)
        coo = _random_coo(rng)
        bucketed = bucket_rows(coo, min_len=4)
        # every rating appears exactly once across buckets
        total = sum(int(b.mask.sum()) for b in bucketed.buckets)
        assert total == coo.nnz
        for b in bucketed.buckets:
            assert b.pad_len % 4 == 0
            # mask counts match true row degrees
            for j, row in enumerate(b.row_ids):
                deg = int((coo.rows == row).sum())
                assert int(b.mask[j].sum()) == deg

    def test_row_cap_keeps_top_values(self):
        rows = np.zeros(10, dtype=np.int32)
        cols = np.arange(10, dtype=np.int32)
        vals = np.arange(10, dtype=np.float32)
        coo = RatingsCOO(rows, cols, vals, 1, 10)
        bucketed = bucket_rows(coo, min_len=4, max_len=4)
        b = bucketed.buckets[0]
        kept = set(b.cols[0][b.mask[0] > 0].tolist())
        assert kept == {6, 7, 8, 9}

    def test_half_step_flops_accounting(self):
        # two rows of degree 3 and 5 pad to lengths 4 and 8 (growth 2)
        rows = np.repeat(np.array([0, 1], dtype=np.int32), [3, 5])
        cols = np.arange(8, dtype=np.int32)
        vals = np.ones(8, dtype=np.float32)
        coo = RatingsCOO(rows, cols, vals, 2, 8)
        bucketed = bucket_rows(coo, min_len=4, growth=2)
        K = 4
        fl = half_step_flops(bucketed, K)
        per_entry = 2 * K * K + 2 * K
        per_solve = K**3 / 3 + 2 * K * K
        assert fl["useful_flops"] == pytest.approx(
            8 * per_entry + 2 * per_solve
        )
        # executed prices the solve at what the default CG actually runs:
        # steps x (2K^2 + 8K) per row (ADVICE r2)
        steps = min(K + 4, als_mod._CG_STEP_CAP)
        per_solve_exec = steps * (2 * K * K + 8 * K)
        assert fl["executed_flops"] == pytest.approx(
            (4 + 8) * per_entry + 2 * per_solve_exec
        )
        # padding overhead strictly bounded by the growth factor on the
        # matmul term; executed >= useful always
        assert fl["executed_flops"] >= fl["useful_flops"]


class TestChunking:
    def test_chunk_decomposition_covers_every_rating(self):
        rng = np.random.default_rng(4)
        # heavy rows force multi-chunk decomposition
        rows = np.concatenate([
            np.repeat(0, 37), np.repeat(1, 9), np.repeat(2, 3),
            np.repeat(3, 16),
        ]).astype(np.int32)
        n = len(rows)
        cols = rng.integers(0, 50, n).astype(np.int32)
        vals = rng.uniform(1, 5, n).astype(np.float32)
        coo = RatingsCOO(rows, cols, vals, 5, 50)
        chunked = chunk_rows(coo, sizes=(16, 4))
        # every rating appears exactly once across chunk slabs
        total = sum(int(s.deg.sum()) for s in chunked.slabs)
        assert total == n
        # row 0 (deg 37): two full 16-chunks + one padded 4-chunk + 1 left
        got = {}
        for s in chunked.slabs:
            L = s.cols.shape[1]
            for j, rid in enumerate(s.row_ids):
                got.setdefault(int(rid), []).append(int(s.deg[j]))
                assert s.deg[j] <= L
                # padding slots hold zero values
                assert (s.vals[j, s.deg[j]:] == 0).all()
        assert sorted(got[0], reverse=True) == [16, 16, 4, 1]
        assert sum(got[1]) == 9 and sum(got[3]) == 16

    def test_chunk_value_multiset_preserved(self):
        rng = np.random.default_rng(8)
        coo = _random_coo(rng, users=12, items=40, density=0.6)
        chunked = chunk_rows(coo, sizes=(8,))
        for u in range(coo.num_rows):
            want = sorted(coo.vals[coo.rows == u].tolist())
            have = sorted(
                v
                for s in chunked.slabs
                for j, rid in enumerate(s.row_ids)
                if rid == u
                for v in s.vals[j, : s.deg[j]].tolist()
            )
            assert have == pytest.approx(want)

    def test_chunked_flops_accounting(self):
        rows = np.repeat(np.array([0, 1], dtype=np.int32), [10, 3])
        coo = RatingsCOO(rows, np.arange(13, dtype=np.int32),
                         np.ones(13, dtype=np.float32), 2, 13)
        K = 4
        fl = half_step_flops(chunk_rows(coo, sizes=(8, 4)), K)
        per_entry = 2 * K * K + 2 * K
        per_solve = K**3 / 3 + 2 * K * K
        # row0: one 8-chunk + one 4-chunk (deg 2); row1: one 4-chunk (deg 3)
        assert fl["useful_flops"] == pytest.approx(13 * per_entry + 2 * per_solve)
        steps = min(K + 4, als_mod._CG_STEP_CAP)
        per_solve_exec = steps * (2 * K * K + 8 * K)
        assert fl["executed_flops"] == pytest.approx(
            (8 + 4 + 4) * per_entry + 2 * per_solve_exec
        )


class TestSolve:
    @pytest.mark.parametrize("implicit", [False, True])
    def test_solve_half_matches_numpy(self, implicit):
        rng = np.random.default_rng(1)
        coo = _random_coo(rng)
        K = 6
        V = rng.standard_normal((coo.num_cols, K)).astype(np.float32)
        bucketed = bucket_rows(coo, min_len=4)
        import jax.numpy as jnp

        got = np.asarray(
            solve_half(jnp.asarray(V), bucketed, K, lam=0.1,
                       implicit=implicit, alpha=10.0)
        )
        want = _numpy_solve_half(V, coo, lam=0.1, implicit=implicit, alpha=10.0)
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)

    @pytest.mark.parametrize("implicit", [False, True])
    def test_chunked_solve_half_matches_numpy(self, implicit):
        """The single-dispatch accumulate-then-solve program computes the
        same normal equations as the per-bucket path and the oracle, incl.
        rows split across multiple chunks."""
        rng = np.random.default_rng(3)
        coo = _random_coo(rng, users=25, items=30, density=0.5)
        K = 6
        V = rng.standard_normal((coo.num_cols, K)).astype(np.float32)
        chunked = chunk_rows(coo, sizes=(8, 4))  # rows of deg>8 multi-chunk
        import jax.numpy as jnp

        got = np.asarray(
            solve_half(jnp.asarray(V), chunked, K, lam=0.1,
                       implicit=implicit, alpha=10.0)
        )
        want = _numpy_solve_half(V, coo, lam=0.1, implicit=implicit, alpha=10.0)
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)

    def test_layout_validation(self):
        rng = np.random.default_rng(0)
        coo = _random_coo(rng, users=5, items=5)
        with pytest.raises(ValueError, match="layout must be"):
            als_train(coo, rank=4, iterations=1, layout="chunkd")
        # bucketed-only knobs on the explicit chunked layout raise;
        # "auto" routes them to bucketed instead
        with pytest.raises(ValueError, match="bucketed-layout knobs"):
            als_train(coo, rank=4, iterations=1, max_row_len=4,
                      layout="chunked")
        f = als_train(coo, rank=4, iterations=1, max_row_len=4)
        assert np.isfinite(np.asarray(f.item)).all()
        # fused rejects the bucketed-only knobs too
        with pytest.raises(ValueError, match="bucketed-layout knobs"):
            als_train(coo, rank=4, iterations=1, hbm_resident=False,
                      layout="fused")

    def test_chunked_zero_rows_and_train_parity(self):
        rng = np.random.default_rng(9)
        coo = _random_coo(rng, users=30, items=20)
        chunked = als_train(coo, rank=6, iterations=6, lam=0.05, seed=2,
                            layout="chunked", chunk_sizes=(8, 4))
        bucketed = als_train(coo, rank=6, iterations=6, lam=0.05, seed=2,
                             layout="bucketed")
        fused = als_train(coo, rank=6, iterations=6, lam=0.05, seed=2,
                          layout="fused")
        np.testing.assert_allclose(
            np.asarray(chunked.user), np.asarray(bucketed.user),
            rtol=5e-3, atol=5e-3,
        )
        np.testing.assert_allclose(
            np.asarray(chunked.item), np.asarray(bucketed.item),
            rtol=5e-3, atol=5e-3,
        )
        # the fused single-program ladder computes the same estimator
        np.testing.assert_allclose(
            np.asarray(fused.user), np.asarray(chunked.user),
            rtol=5e-3, atol=5e-3,
        )
        np.testing.assert_allclose(
            np.asarray(fused.item), np.asarray(chunked.item),
            rtol=5e-3, atol=5e-3,
        )

    def test_train_reduces_rmse_and_reconstructs(self):
        rng = np.random.default_rng(2)
        # low-rank ground truth -> ALS should fit it well
        U0 = rng.standard_normal((40, 4)).astype(np.float32)
        V0 = rng.standard_normal((25, 4)).astype(np.float32)
        full = U0 @ V0.T
        mask = rng.random(full.shape) < 0.5
        rows, cols = np.nonzero(mask)
        coo = RatingsCOO(
            rows.astype(np.int32), cols.astype(np.int32),
            full[rows, cols].astype(np.float32), 40, 25,
        )
        factors = als_train(coo, rank=8, iterations=10, lam=0.01, seed=0)
        assert rmse(factors, coo) < 0.15

    def test_zero_rating_rows_get_zero_factors(self):
        coo = RatingsCOO(
            np.array([0, 2], dtype=np.int32),
            np.array([0, 1], dtype=np.int32),
            np.array([3.0, 4.0], dtype=np.float32),
            num_rows=4, num_cols=2,
        )
        factors = als_train(coo, rank=3, iterations=2, lam=0.1)
        u = np.asarray(factors.user)
        assert np.allclose(u[1], 0) and np.allclose(u[3], 0)
        assert not np.allclose(u[0], 0)

    @pytest.mark.parametrize("layout", ["chunked", "bucketed", "fused"])
    def test_sharded_matches_single_device(self, mesh8, layout):
        rng = np.random.default_rng(3)
        coo = _random_coo(rng, users=32, items=16)
        single = als_train(coo, rank=4, iterations=3, lam=0.05, seed=1,
                           layout=layout)
        sharded = als_train(coo, rank=4, iterations=3, lam=0.05, seed=1,
                            mesh=mesh8, layout=layout)
        np.testing.assert_allclose(
            np.asarray(single.user), np.asarray(sharded.user),
            rtol=1e-4, atol=1e-4,
        )

    def test_implicit_training_ranks_observed_higher(self):
        rng = np.random.default_rng(4)
        # two user groups each consuming one item group
        rows, cols = [], []
        for u in range(20):
            group = u % 2
            for i in range(10):
                if rng.random() < 0.8:
                    rows.append(u)
                    cols.append(group * 10 + i)
        coo = RatingsCOO(
            np.asarray(rows, dtype=np.int32), np.asarray(cols, dtype=np.int32),
            np.ones(len(rows), dtype=np.float32), 20, 20,
        )
        factors = als_train(coo, rank=6, iterations=8, lam=0.1,
                            implicit=True, alpha=20.0, seed=0)
        scores = np.asarray(factors.user) @ np.asarray(factors.item).T
        in_group = scores[0, :10].mean()
        out_group = scores[0, 10:].mean()
        assert in_group > out_group + 0.1

    def test_implicit_negative_ratings_are_dislikes(self):
        """MLlib trainImplicit semantics: r < 0 is a high-confidence ZERO
        preference (c = 1 + α|r|, p = [r > 0]) and r = 0 contributes
        nothing — the like/dislike pattern of the reference's
        similarproduct "multi" variant (LikeAlgorithm.scala: like -> 1,
        dislike -> -1 into trainImplicit)."""
        rng = np.random.default_rng(2)
        rows, cols, vals = [], [], []
        for u in range(24):
            for i in range(8):           # everyone likes group 0
                if rng.random() < 0.8:
                    rows.append(u), cols.append(i), vals.append(1.0)
            for i in range(8, 16):       # everyone dislikes group 1
                if rng.random() < 0.8:
                    rows.append(u), cols.append(i), vals.append(-1.0)
        coo = RatingsCOO(np.asarray(rows, np.int32), np.asarray(cols, np.int32),
                         np.asarray(vals, np.float32), 24, 16)
        f = als_train(coo, rank=4, iterations=8, lam=0.1, implicit=True,
                      alpha=10.0, seed=0)
        scores = np.asarray(f.user) @ np.asarray(f.item).T
        assert scores[:, :8].mean() > scores[:, 8:].mean() + 0.3

        # r = 0 entries are no-ops: adding them changes nothing
        z = RatingsCOO(
            np.concatenate([coo.rows, np.asarray([0, 5], np.int32)]),
            np.concatenate([coo.cols, np.asarray([3, 12], np.int32)]),
            np.concatenate([coo.vals, np.asarray([0.0, 0.0], np.float32)]),
            24, 16)
        fz = als_train(z, rank=4, iterations=8, lam=0.1, implicit=True,
                       alpha=10.0, seed=0)
        np.testing.assert_allclose(np.asarray(f.user), np.asarray(fz.user),
                                   rtol=1e-4, atol=1e-4)


class TestPredictAndModel:
    def _model(self, rng):
        from predictionio_tpu.models.als import ALSModel
        from predictionio_tpu.utils.bimap import EntityIdIxMap
        import jax.numpy as jnp

        U, I, K = 5, 12, 4
        uf = rng.standard_normal((U, K)).astype(np.float32)
        itf = rng.standard_normal((I, K)).astype(np.float32)
        return ALSModel(
            rank=K,
            user_factors=jnp.asarray(uf),
            item_factors=jnp.asarray(itf),
            user_ids=EntityIdIxMap.from_ids([f"u{i}" for i in range(U)]),
            item_ids=EntityIdIxMap.from_ids([f"i{i}" for i in range(I)]),
            seen_by_user={0: np.asarray([0, 1], dtype=np.int32)},
        )

    def test_recommend_excludes_seen_and_orders(self):
        rng = np.random.default_rng(5)
        m = self._model(rng)
        recs = m.recommend("u0", 5)
        names = [r[0] for r in recs]
        assert "i0" not in names and "i1" not in names
        scores = [r[1] for r in recs]
        assert scores == sorted(scores, reverse=True)
        # brute-force check of the winner
        uf = np.asarray(m.user_factors)[0]
        itf = np.asarray(m.item_factors)
        full = itf @ uf
        full[[0, 1]] = -np.inf
        assert names[0] == f"i{int(np.argmax(full))}"

    def test_recommend_unknown_user_empty(self):
        rng = np.random.default_rng(6)
        assert self._model(rng).recommend("nobody", 3) == []

    def test_recommend_seen_overflow_never_truncates(self):
        """exclude_seen is a correctness contract: a history longer than
        the packed serving buffer (_SEEN_PAD) must fold the overflow
        into the allow vector, not silently re-recommend seen items."""
        from predictionio_tpu.models import als as mals
        from predictionio_tpu.models.als import ALSModel
        from predictionio_tpu.utils.bimap import EntityIdIxMap
        import jax.numpy as jnp

        rng = np.random.default_rng(7)
        U, I, K = 2, mals._SEEN_PAD + 40, 4
        m = ALSModel(
            rank=K,
            user_factors=jnp.asarray(
                rng.standard_normal((U, K)).astype(np.float32)),
            item_factors=jnp.asarray(
                rng.standard_normal((I, K)).astype(np.float32)),
            user_ids=EntityIdIxMap.from_ids([f"u{i}" for i in range(U)]),
            item_ids=EntityIdIxMap.from_ids([f"i{i}" for i in range(I)]),
            # u0 has seen everything except the last 10 items
            seen_by_user={0: np.arange(I - 10, dtype=np.int32)},
        )
        recs = m.recommend("u0", 10)
        names = {r[0] for r in recs}
        assert names == {f"i{i}" for i in range(I - 10, I)}, names

    def test_allow_filter(self):
        rng = np.random.default_rng(7)
        m = self._model(rng)
        allow = np.zeros(12, dtype=np.float32)
        allow[[3, 4]] = 1.0
        names = {r[0] for r in m.recommend("u1", 5, allow=allow)}
        assert names <= {"i3", "i4"} and names

    def test_similar_excludes_query(self):
        rng = np.random.default_rng(8)
        m = self._model(rng)
        sims = m.similar(["i2"], 4)
        assert "i2" not in [s[0] for s in sims]
        assert len(sims) == 4
        # cosine winner check
        itf = np.asarray(m.item_factors)
        q = itf[2] / np.linalg.norm(itf[2])
        itn = itf / np.linalg.norm(itf, axis=1, keepdims=True)
        cos = itn @ q
        cos[2] = -np.inf
        assert sims[0][0] == f"i{int(np.argmax(cos))}"

    def test_similar_unknown_items_empty(self):
        rng = np.random.default_rng(9)
        assert self._model(rng).similar(["zzz"], 3) == []

    def test_save_load_roundtrip(self, tmp_path):
        rng = np.random.default_rng(10)
        m = self._model(rng)
        m.save(str(tmp_path / "model"))
        from predictionio_tpu.models.als import ALSModel

        m2 = ALSModel.load(str(tmp_path / "model"))
        assert m2.rank == m.rank
        np.testing.assert_array_equal(
            np.asarray(m2.user_factors), np.asarray(m.user_factors)
        )
        assert m2.recommend("u0", 3) == m.recommend("u0", 3)

    # -- the bfloat16 serving copy of the item table (PR 35) --------------
    def test_serving_copy_is_made_once_in_bfloat16(self):
        m = self._model(np.random.default_rng(12))
        assert m._serving_items is None
        cols = np.zeros((2, 8), np.int32)
        mask = np.zeros((2, 8), np.float32)
        m.batch_topk(np.asarray([0, 1], np.int32), cols, mask, None, 3)
        assert m._serving_items is not None
        served = m.serving_item_factors()
        assert served.dtype == jnp.bfloat16
        assert served.shape == m.item_factors.shape
        np.testing.assert_array_equal(
            np.asarray(served),
            np.asarray(m.item_factors.astype(jnp.bfloat16)))
        m.batch_topk(np.asarray([2, 3], np.int32), cols, mask, None, 3)
        m.recommend("u0", 3)
        assert m.serving_item_factors() is served
        # the model's own table is untouched, and is what the gauge is not
        assert m.item_factors.dtype == jnp.float32
        assert m.score_table_bytes_per_entry == 2

    def test_serving_copy_of_a_bfloat16_table_is_the_table(self):
        m = self._model(np.random.default_rng(13))
        m.item_factors = m.item_factors.astype(jnp.bfloat16)
        assert m.serving_item_factors() is m.item_factors

    def test_serving_copy_follows_a_replaced_table(self):
        """``dataclasses.replace(model, item_factors=...)`` (the
        weighted-items example scales the table so) carries the cache
        field over: the copy must be of the new table."""
        import dataclasses

        m = self._model(np.random.default_rng(19))
        before = m.serving_item_factors()
        scaled = dataclasses.replace(m, item_factors=m.item_factors * 3.0)
        np.testing.assert_array_equal(
            np.asarray(scaled.serving_item_factors()),
            np.asarray((m.item_factors * 3.0).astype(jnp.bfloat16)))
        assert m.serving_item_factors() is before

    def test_serving_copy_keeps_a_row_sharded_tables_sharding(self, mesh8):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        m = self._model(np.random.default_rng(14))
        ways = int(mesh8.shape["model"])
        rows = NamedSharding(mesh8, P("model", None))
        table = np.asarray(m.item_factors)[:12 // ways * ways]
        m.item_factors = jax.device_put(table, rows)
        served = m.serving_item_factors()
        assert served.dtype == jnp.bfloat16
        assert served.sharding == m.item_factors.sharding

    def test_serving_copy_is_never_serialized(self, tmp_path):
        import pickle

        from predictionio_tpu.models.als import ALSModel
        from predictionio_tpu.utils.checkpoint import load_sharded

        m = self._model(np.random.default_rng(15))
        before = m.recommend("u0", 3)
        assert m._serving_items is not None
        assert m.__getstate__()["_serving_items"] is None
        again = pickle.loads(pickle.dumps(m))
        assert again._serving_items is None
        assert again.recommend("u0", 3) == before
        # the checkpoint holds the two float32 tables and nothing else
        m.save(str(tmp_path / "model"))
        arrays = load_sharded(str(tmp_path / "model"))
        assert sorted(arrays) == ["item", "user"]
        assert all(a.dtype == np.float32 for a in arrays.values())
        m2 = ALSModel.load(str(tmp_path / "model"))
        assert m2.item_factors.dtype == jnp.float32
        assert m2._serving_items is None
        assert m2.recommend("u0", 3) == before
        assert m2.serving_item_factors().dtype == jnp.bfloat16

    def test_predict_rating_and_similar_still_read_float32(self):
        rng = np.random.default_rng(16)
        m = self._model(rng)
        m.recommend("u0", 3)                      # the copy exists
        uf = np.asarray(m.user_factors, np.float64)
        itf = np.asarray(m.item_factors, np.float64)
        # a bfloat16 operand would be off by up to 2^-8 of each product
        assert m.predict_rating("u1", "i3") == pytest.approx(
            uf[1] @ itf[3], rel=1e-6, abs=1e-6)
        itn = itf / np.linalg.norm(itf, axis=1, keepdims=True)
        for name, score in m.similar(["i2"], 4):
            assert score == pytest.approx(itn[int(name[1:])] @ itn[2],
                                          abs=1e-6)

    @pytest.mark.parametrize("user", [0, 1, 4])
    def test_recommend_and_batch_topk_give_the_same_ids(self, user):
        """Both read the serving copy, so an answer does not depend on
        whether batching is on."""
        m = self._model(np.random.default_rng(17))
        seen = m.seen_by_user.get(user, np.empty(0, np.int32))
        cols = np.zeros((1, 8), np.int32)
        mask = np.zeros((1, 8), np.float32)
        cols[0, :len(seen)], mask[0, :len(seen)] = seen, 1.0
        vals, idxs = m.batch_topk(np.asarray([user], np.int32), cols, mask,
                                  None, 5)
        single = m.recommend(f"u{user}", 5)
        assert [name for name, _ in single] == [
            f"i{i}" for i in np.asarray(idxs)[0]]
        np.testing.assert_allclose([v for _, v in single],
                                   np.asarray(vals)[0], rtol=1e-6)

    def test_serving_stats_reports_the_score_tables_width(self):
        """``scoreTableBytesPerEntry``: 0 until a server wires a model
        (tests/test_topk.py reads 2 behind a live one), then what it
        was told."""
        from predictionio_tpu.api.stats import ServingStats
        from predictionio_tpu.obs.registry import serving_collector

        stats = ServingStats()
        assert stats.snapshot()["scoreTableBytesPerEntry"] == 0
        m = self._model(np.random.default_rng(18))
        stats.set_score_table_bytes(m.score_table_bytes_per_entry)
        assert stats.snapshot()["scoreTableBytesPerEntry"] == 2
        assert stats.score_table_bytes() == 2
        gauge = [x for x in serving_collector(stats)()
                 if x.name == "pio_serving_score_table_bytes_per_entry"]
        assert [x.samples for x in gauge] == [[({}, 2.0)]]
        # not a counter: it does not ride the *_total exposition
        assert "score_table_bytes" not in stats.raw_counts()

    def test_predict_ratings_pairs(self):
        rng = np.random.default_rng(11)
        m = self._model(rng)
        import jax.numpy as jnp

        got = np.asarray(
            predict_ratings(
                m.user_factors, m.item_factors,
                jnp.asarray([0, 1]), jnp.asarray([2, 3]),
            )
        )
        uf = np.asarray(m.user_factors)
        itf = np.asarray(m.item_factors)
        np.testing.assert_allclose(got[0], uf[0] @ itf[2], rtol=1e-5)
        np.testing.assert_allclose(got[1], uf[1] @ itf[3], rtol=1e-5)


class TestNativeBucketizer:
    """native/bucketize.cc vs the NumPy fallback: identical slab layout."""

    def test_native_matches_python(self):
        rng = np.random.default_rng(3)
        nnz = 20_000
        coo = RatingsCOO(
            (400 * rng.random(nnz) ** 1.5).astype(np.int32),
            (300 * rng.random(nnz) ** 1.5).astype(np.int32),
            rng.random(nnz).astype(np.float32) * 5,
            400, 300,
        )
        nat = bucket_rows(coo, min_len=8, max_len=64)
        py = bucket_rows(coo, min_len=8, max_len=64, use_native=False)
        assert [b.pad_len for b in nat.buckets] == [b.pad_len for b in py.buckets]
        for bn, bp in zip(nat.buckets, py.buckets):
            on, op = np.argsort(bn.row_ids), np.argsort(bp.row_ids)
            np.testing.assert_array_equal(bn.row_ids[on], bp.row_ids[op])
            np.testing.assert_array_equal(bn.deg[on], bp.deg[op])
            for j in range(len(on)):
                a, b = on[j], op[j]
                da, db = int(bn.deg[a]), int(bp.deg[b])
                sa = sorted(zip(bn.cols[a][:da].tolist(), bn.vals[a][:da].tolist()))
                sb = sorted(zip(bp.cols[b][:db].tolist(), bp.vals[b][:db].tolist()))
                if da < 64:
                    assert sa == sb
                else:  # capped rows keep the same top-value multiset
                    assert sorted(v for _, v in sa) == sorted(v for _, v in sb)
            # padding stays zeroed
            assert (bn.cols * (1 - bn.mask)).sum() == 0
            assert (bn.vals * (1 - bn.mask)).sum() == 0

    def test_native_ladder_matches_python(self):
        """pio_ladder (the fused layout's packer, measured ~6.7x the
        NumPy path at ML-20M scale) must produce the identical slab
        layout, including beyond-base-ladder degrees."""
        from predictionio_tpu.ops.als import ladder_rows

        rng = np.random.default_rng(4)
        nnz = 40_000
        rows = (500 * rng.random(nnz) ** 1.8).astype(np.int32)
        cols = (300 * rng.random(nnz) ** 1.8).astype(np.int32)
        vals = rng.random(nnz).astype(np.float32) * 5
        # one row heavier than the base ladder (2048 * width = 32768
        # entries at width=16) so the doubling-extension branch runs in
        # BOTH implementations
        heavy = 40_000
        rows = np.concatenate([rows, np.full(heavy, 501, np.int32)])
        cols = np.concatenate([cols, (np.arange(heavy) % 300).astype(np.int32)])
        vals = np.concatenate([vals, np.ones(heavy, np.float32)])
        coo = RatingsCOO(rows, cols, vals, 502, 300)
        nat = ladder_rows(coo, width=16, small=8)
        py = ladder_rows(coo, width=16, small=8, use_native=False)
        assert nat.buckets[-1].pad_len > 2048 * 16  # extension engaged
        assert [b.pad_len for b in nat.buckets] == \
               [b.pad_len for b in py.buckets]
        for bn, bp in zip(nat.buckets, py.buckets):
            np.testing.assert_array_equal(bn.row_ids, bp.row_ids)
            np.testing.assert_array_equal(bn.deg, bp.deg)
            np.testing.assert_array_equal(bn.cols, bp.cols)
            np.testing.assert_array_equal(bn.vals, bp.vals)

    def test_empty_and_fallback(self):
        coo = RatingsCOO(np.zeros(0, np.int32), np.zeros(0, np.int32),
                         np.zeros(0, np.float32), 4, 4)
        assert bucket_rows(coo).buckets == ()


class TestNativeChunker:
    """native/bucketize.cc pio_chunk* vs the NumPy chunk_rows fallback:
    identical slab layout, chunk order, and padding."""

    def test_native_matches_python(self):
        rng = np.random.default_rng(5)
        nnz = 20_000
        coo = RatingsCOO(
            (400 * rng.random(nnz) ** 1.5).astype(np.int32),
            (300 * rng.random(nnz) ** 1.5).astype(np.int32),
            rng.random(nnz).astype(np.float32) * 5,
            400, 300,
        )
        for sizes in ((16, 4), (64, 16, 4), (8,)):
            nat = chunk_rows(coo, sizes)
            py = chunk_rows(coo, sizes, use_native=False)
            assert [s.cols.shape for s in nat.slabs] == \
                [s.cols.shape for s in py.slabs]
            for sn, sp in zip(nat.slabs, py.slabs):
                np.testing.assert_array_equal(sn.row_ids, sp.row_ids)
                np.testing.assert_array_equal(sn.deg, sp.deg)
                # same entry multiset per chunk (order within a chunk is
                # row-sorted in both; compare exactly)
                np.testing.assert_array_equal(sn.cols, sp.cols)
                np.testing.assert_array_equal(sn.vals, sp.vals)

    def test_empty_coo_falls_back(self):
        coo = RatingsCOO(np.zeros(0, np.int32), np.zeros(0, np.int32),
                         np.zeros(0, np.float32), 4, 4)
        assert chunk_rows(coo).slabs == ()


class TestHighRankSolver:
    """CG accuracy at BASELINE rank 200 against the exact oracle
    (ADVICE r2: nothing validated the default step cap above rank 24)."""

    @staticmethod
    def _normal_systems(rng, batch, rank, deg_lo, deg_hi, lam=0.08):
        """Ridge-regularised ALS-WR normal matrices from realistic
        degrees: A = FᵀF + lam*deg*I, b = Fᵀ r."""
        A = np.empty((batch, rank, rank), dtype=np.float32)
        b = np.empty((batch, rank), dtype=np.float32)
        for j in range(batch):
            deg = int(rng.integers(deg_lo, deg_hi))
            F = (rng.standard_normal((deg, rank)) / np.sqrt(rank)).astype(
                np.float32)
            r = rng.integers(1, 6, size=deg).astype(np.float32)
            A[j] = F.T @ F + lam * deg * np.eye(rank, dtype=np.float32)
            b[j] = F.T @ r
        return A, b

    def test_rank200_cg_matches_f64_oracle_at_default_cap(self):
        from predictionio_tpu.ops.als import (
            _cg_solve_batched,
            _cho_solve_batched,
        )

        rng = np.random.default_rng(0)
        A, b = self._normal_systems(rng, batch=48, rank=200,
                                    deg_lo=800, deg_hi=2000)
        exact = np.linalg.solve(
            A.astype(np.float64), b.astype(np.float64)[..., None])[..., 0]
        norm = np.linalg.norm(exact, axis=-1)

        cg = np.asarray(_cg_solve_batched(jnp.asarray(A), jnp.asarray(b)))
        cg_err = np.linalg.norm(cg - exact, axis=-1) / norm
        # the docstring's measured f32 plateau band (<= ~1e-2 rel)
        assert cg_err.max() < 2e-2, f"CG rel err {cg_err.max():.2e}"

        # ...and within a small factor of what an exact f32 DIRECT solve
        # achieves on the same systems (the plateau is conditioning-, not
        # solver-, bound)
        cho = np.asarray(_cho_solve_batched(jnp.asarray(A), jnp.asarray(b)))
        cho_err = np.linalg.norm(cho - exact, axis=-1) / norm
        assert cg_err.max() < max(10 * cho_err.max(), 5e-3), (
            f"CG {cg_err.max():.2e} vs f32-direct {cho_err.max():.2e}"
        )

    def test_cholesky_solver_opt_in_matches_cg(self):
        rng = np.random.default_rng(5)
        coo = _random_coo(rng, users=40, items=25)
        # f32 build isolates the solver comparison from bf16 einsum noise
        cg = als_train(coo, rank=6, iterations=4, lam=0.05, seed=1,
                       matmul_dtype="float32")
        cho = als_train(coo, rank=6, iterations=4, lam=0.05, seed=1,
                        matmul_dtype="float32", solver="cholesky")
        np.testing.assert_allclose(
            np.asarray(cg.user), np.asarray(cho.user), rtol=2e-3, atol=2e-3)
        # the chunked accumulator path has no direct-solve variant
        with pytest.raises(ValueError, match="cholesky"):
            als_train(coo, rank=6, iterations=1, layout="chunked",
                      solver="cholesky")


def test_bf16_matmul_close_to_f32():
    """als_train(matmul_dtype="bfloat16"): native-MXU-rate normal
    equations; factor quality must stay within tolerance of f32."""
    rng = np.random.default_rng(7)
    nnz = 20_000
    coo = RatingsCOO(
        (300 * rng.random(nnz) ** 1.4).astype(np.int32),
        (200 * rng.random(nnz) ** 1.4).astype(np.int32),
        (rng.integers(1, 11, nnz) / 2).astype(np.float32), 300, 200,
    )
    f32 = als_train(coo, rank=8, iterations=6, lam=0.05, seed=3)
    bf = als_train(coo, rank=8, iterations=6, lam=0.05, seed=3,
                   matmul_dtype="bfloat16")
    assert abs(rmse(f32, coo) - rmse(bf, coo)) < 0.02


def test_sharded_factor_table_matches_replicated():
    """Tensor-parallel layout: V row-sharded over the "model" axis must
    give the same solution as replicated V (XLA inserts the gathers)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual mesh")
    rng = np.random.default_rng(5)
    nnz = 8_000
    coo = RatingsCOO(
        (64 * rng.random(nnz)).astype(np.int32),
        (48 * rng.random(nnz)).astype(np.int32),
        rng.random(nnz).astype(np.float32) * 5, 64, 48,
    )
    b = bucket_rows(coo, min_len=8)
    V = jnp.asarray(rng.standard_normal((48, 8)).astype(np.float32))
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(4, 2), ("data", "model"))
    rep = np.asarray(solve_half(V, b, 8, 0.05, mesh=mesh))
    tp = np.asarray(solve_half(V, b, 8, 0.05, mesh=mesh, shard_factors=True))
    np.testing.assert_allclose(rep, tp, atol=1e-5)


def test_stale_native_library_falls_back_to_numpy(monkeypatch):
    """A cached/prebuilt _bucketize.so missing the newer pio_chunk*
    symbols must register as 'no native path' (NumPy fallback), not
    crash every bucket_rows/chunk_rows call (AttributeError on dlsym)."""
    import predictionio_tpu.native as native

    class _StaleLib:
        def __getattr__(self, name):
            if name.startswith("pio_chunk"):
                raise AttributeError(name)  # symbol missing in old .so
            return lambda *a: None

    monkeypatch.setattr(native, "_bucketize_lib", None)
    monkeypatch.setattr(native, "_bucketize_failed", False)
    assert native._bind_bucketize(_StaleLib()) is None
    assert native._bucketize_failed is True
    # and the layout builders still work (NumPy path)
    rng = np.random.default_rng(0)
    coo = _random_coo(rng, users=10, items=8)
    monkeypatch.setattr(
        "predictionio_tpu.native.load_bucketize", lambda: None)
    assert sum(int(s.deg.sum()) for s in chunk_rows(coo, (8,)).slabs) == coo.nnz


def test_fused_tp_factor_tables_are_model_sharded(mesh8):
    """The DP×MP tensor-parallel layout on the FUSED (default) path
    (VERDICT r3 missing #1; BASELINE's sharded-embeddings config): both
    result tables must be genuinely row-sharded over the "model" axis —
    per-device shards hold num_rows/model_axis rows — and match the
    single-device factors."""
    import jax

    rng = np.random.default_rng(9)
    nnz = 12_000
    users, items = 96, 64        # divisible by model axis (2): exact shards
    coo = RatingsCOO(
        (users * rng.random(nnz) ** 1.6).astype(np.int32),
        (items * rng.random(nnz) ** 1.6).astype(np.int32),
        rng.random(nnz).astype(np.float32) * 5, users, items,
    )
    single = als_train(coo, rank=8, iterations=3, lam=0.05, seed=1,
                       layout="fused", matmul_dtype="float32")
    tp = als_train(coo, rank=8, iterations=3, lam=0.05, seed=1,
                   mesh=mesh8, layout="fused", shard_factors=True,
                   matmul_dtype="float32")
    np.testing.assert_allclose(
        np.asarray(single.user), np.asarray(tp.user), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(
        np.asarray(single.item), np.asarray(tp.item), rtol=2e-4, atol=2e-4)

    model_ax = int(mesh8.shape["model"])
    for table, n in ((tp.user, users), (tp.item, items)):
        spec = table.sharding.spec
        assert spec[0] == "model", f"table not model-sharded: {spec}"
        shard_rows = {s.data.shape[0] for s in table.addressable_shards}
        assert shard_rows == {n // model_ax}, (
            f"expected {n // model_ax}-row shards, got {shard_rows}")


def test_fused_tp_handles_nondivisible_rows_and_implicit(mesh8):
    """Row counts that don't divide the model axis pad internally and
    slice back; implicit mode's gramian must ignore the pad rows."""
    rng = np.random.default_rng(11)
    nnz = 6_000
    users, items = 91, 53        # NOT divisible by model axis
    coo = RatingsCOO(
        (users * rng.random(nnz) ** 1.6).astype(np.int32),
        (items * rng.random(nnz) ** 1.6).astype(np.int32),
        (rng.random(nnz) * 4 + 1).astype(np.float32), users, items,
    )
    for implicit in (False, True):
        single = als_train(coo, rank=4, iterations=2, lam=0.05, seed=2,
                           implicit=implicit, alpha=8.0, layout="fused",
                           matmul_dtype="float32")
        tp = als_train(coo, rank=4, iterations=2, lam=0.05, seed=2,
                       implicit=implicit, alpha=8.0, mesh=mesh8,
                       layout="fused", shard_factors=True,
                       matmul_dtype="float32")
        assert np.asarray(tp.user).shape == (users, 4)
        assert np.asarray(tp.item).shape == (items, 4)
        np.testing.assert_allclose(
            np.asarray(single.user), np.asarray(tp.user),
            rtol=2e-4, atol=2e-4, err_msg=f"implicit={implicit}")
        np.testing.assert_allclose(
            np.asarray(single.item), np.asarray(tp.item),
            rtol=2e-4, atol=2e-4, err_msg=f"implicit={implicit}")


class TestBf16CGMatvec:
    def test_bf16_matvec_within_measured_band_vs_f64_oracle(self):
        """The bf16 A-matvec CG (rank-200 auto policy) must stay inside
        the measured ~2.5e-3 relative band vs an f64 oracle on both
        system families (round-4 probe; _cg_solve_batched docstring)."""
        from predictionio_tpu.ops.als import _cg_solve_batched

        rng = np.random.default_rng(0)
        for lo, hi, lam in ((800, 2000, 0.08), (100, 400, 0.01)):
            A, b = TestHighRankSolver._normal_systems(
                rng, batch=32, rank=200, deg_lo=lo, deg_hi=hi, lam=lam)
            exact = np.linalg.solve(
                A.astype(np.float64), b.astype(np.float64)[..., None]
            )[..., 0]
            norm = np.linalg.norm(exact, axis=-1)
            bf = np.asarray(_cg_solve_batched(
                jnp.asarray(A), jnp.asarray(b), bf16_matvec=True))
            err = (np.linalg.norm(bf - exact, axis=-1) / norm).max()
            assert err < 5e-3, f"bf16-matvec CG rel err {err:.2e}"

    def test_auto_policy_resolves_by_rank(self):
        from predictionio_tpu.ops.als import (
            _CG_BF16_RANK,
            _resolve_cg_matvec,
        )

        assert _resolve_cg_matvec("auto", 200) is True
        assert _resolve_cg_matvec("auto", _CG_BF16_RANK) is True
        assert _resolve_cg_matvec("auto", 32) is False
        assert _resolve_cg_matvec("float32", 200) is False
        assert _resolve_cg_matvec("bfloat16", 8) is True
        with pytest.raises(ValueError, match="cg_matvec_dtype"):
            _resolve_cg_matvec("fp8", 200)

    def test_high_rank_quality_matches_f32_cg(self):
        """End-to-end: rank-96 training (auto -> bf16 matvec) reaches
        the same reconstruction quality as the forced-f32 run. The
        ITERATES are not compared pointwise — alternation amplifies any
        per-solve perturbation into different (equally good) factor
        trajectories; RMSE is the estimator-level gate."""
        rng = np.random.default_rng(7)
        coo = _random_coo(rng, users=48, items=30, density=0.5)
        bf = als_train(coo, rank=96, iterations=3, lam=0.05, seed=1,
                       matmul_dtype="float32")          # cg auto -> bf16
        f32 = als_train(coo, rank=96, iterations=3, lam=0.05, seed=1,
                        matmul_dtype="float32",
                        cg_matvec_dtype="float32")
        r_bf, r_f32 = rmse(bf, coo), rmse(f32, coo)
        assert abs(r_bf - r_f32) < 5e-3, (r_bf, r_f32)


def test_cg_survives_singular_system_with_bf16_matvec():
    """Negative-curvature guard (round-4 review): on a singular system
    the bf16 matvec's rounding can push p.Ap <= 0 — CG must take a zero
    step (finite iterate), never an exploding one."""
    from predictionio_tpu.ops.als import _cg_solve_batched

    rng = np.random.default_rng(2)
    v = rng.standard_normal(16).astype(np.float32)
    A = np.outer(v, v)[None] * 1e-4          # rank-1, near-zero: singular
    b = rng.standard_normal((1, 16)).astype(np.float32)
    for bf16 in (False, True):
        x = np.asarray(_cg_solve_batched(
            jnp.asarray(A), jnp.asarray(b), steps=16, bf16_matvec=bf16))
        assert np.isfinite(x).all(), (bf16, x)
