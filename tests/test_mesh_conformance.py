"""Multi-device mesh conformance (the `mesh` lane, ISSUE 19): the
DP×MP factor-sharding story must hold on EVERY mesh shape an operator
can deploy over 8 devices — 1×8 (all-model, the serving default), 2×4,
and 4×2 (the training default) — not just the topology the other
suites happen to use.

Three layers:

- **kernel**: ``recommend_topk_sharded`` equals the flat reference
  dispatch per shape, including the two latent failures ROADMAP item 1
  named — ``k`` larger than a shard's rows (tall-skinny 1×8 meshes)
  and a query batch that does not divide the ``data`` axis (B=1
  single-query serving on a 2-wide data axis);
- **train**: fused ``shard_factors=True`` factors match the replicated
  run per shape (in-process, on the conftest 8-device topology);
- **process**: the ``run_mesh_child`` subprocess child re-proves train
  parity AND the save → auto-reshard load → sharded-serving-equals-
  brute pipeline in a fresh jax process driven purely by the
  ``PIO_TRAIN_SHARD_FACTORS`` env knob, the way `pio train`/`pio
  deploy` would.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp
from jax.sharding import Mesh

from predictionio_tpu.ops.topk import recommend_topk, recommend_topk_sharded

pytestmark = pytest.mark.mesh

MESH_SHAPES = ((1, 8), (2, 4), (4, 2))


def _mesh(shape):
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual mesh")
    return Mesh(np.asarray(jax.devices()).reshape(shape),
                ("data", "model"))


def _setup(B, I, K=8, S=16, seed=0):
    rng = np.random.default_rng(seed)
    uv = jnp.asarray(rng.standard_normal((B, K)).astype(np.float32))
    itf = jnp.asarray(rng.standard_normal((I, K)).astype(np.float32))
    cols = jnp.asarray(rng.integers(0, I, (B, S)).astype(np.int32))
    mask = jnp.asarray((rng.random((B, S)) < 0.5).astype(np.float32))
    allow = jnp.asarray((rng.random(I) < 0.9).astype(np.float32))
    return uv, itf, cols, mask, allow


def _assert_topk_equal(sharded, reference):
    v_sh, i_sh = sharded
    v_1, i_1 = reference
    np.testing.assert_allclose(np.asarray(v_sh), np.asarray(v_1),
                               rtol=1e-6, atol=1e-6)
    finite = np.isfinite(np.asarray(v_1))
    np.testing.assert_array_equal(np.asarray(i_sh)[finite],
                                  np.asarray(i_1)[finite])


class TestShardedTopkEveryMeshShape:
    @pytest.mark.parametrize("shape", MESH_SHAPES,
                             ids=lambda s: f"{s[0]}x{s[1]}")
    def test_matches_flat_dispatch(self, shape):
        mesh = _mesh(shape)
        B, I, k = 8, 64, 5
        args = _setup(B, I)
        _assert_topk_equal(
            recommend_topk_sharded(*args, k, mesh),
            recommend_topk(*args, k))

    @pytest.mark.parametrize("shape", MESH_SHAPES,
                             ids=lambda s: f"{s[0]}x{s[1]}")
    def test_k_exceeding_shard_rows(self, shape):
        """The tall-skinny latent failure: on 1×8 a 64-item catalog has
        8-row shards, so any serving k > 8 used to crash the local
        ``lax.top_k``. The local k clamps to shard rows and the merge
        must still recover the exact global top-k."""
        mesh = _mesh(shape)
        B, I, k = 8, 64, 20          # k > 64/8 rows-per-shard
        args = _setup(B, I, seed=2)
        _assert_topk_equal(
            recommend_topk_sharded(*args, k, mesh),
            recommend_topk(*args, k))

    @pytest.mark.parametrize("shape", MESH_SHAPES,
                             ids=lambda s: f"{s[0]}x{s[1]}")
    @pytest.mark.parametrize("B", (1, 3))
    def test_batch_not_dividing_data_axis(self, shape, B):
        """The other latent failure: shard_map rejects a query batch
        that does not divide the "data" axis, so B=1 single-query
        serving crashed on any mesh with data > 1. The entry pads with
        zero query rows and slices them back off."""
        mesh = _mesh(shape)
        I, k = 64, 5
        args = _setup(B, I, seed=4)
        _assert_topk_equal(
            recommend_topk_sharded(*args, k, mesh),
            recommend_topk(*args, k))

    def test_k_larger_than_catalog_clamps(self):
        """k > I follows the shared clamp-not-assert serving contract
        (recommend_topk clamps too) — returns I columns."""
        mesh = _mesh((1, 8))
        args = _setup(4, 16, seed=5)
        vals, idxs = recommend_topk_sharded(*args, 300, mesh)
        assert vals.shape == (4, 16)
        _assert_topk_equal((vals, idxs), recommend_topk(*args, 16))


class TestShardedTrainEveryMeshShape:
    @pytest.mark.parametrize("shape", MESH_SHAPES,
                             ids=lambda s: f"{s[0]}x{s[1]}")
    def test_fused_sharded_matches_replicated(self, shape):
        """Fused DP×MP factors == replicated factors on every mesh
        shape (test_als.py pins 4×2 in depth; this pins the shapes an
        operator can actually pick, incl. the all-model 1×8)."""
        from predictionio_tpu.ops.als import RatingsCOO, als_train

        mesh = _mesh(shape)
        rng = np.random.default_rng(13)
        nnz = 6_000
        users, items = 64, 48        # divide every model width exactly
        coo = RatingsCOO(
            (users * rng.random(nnz) ** 1.6).astype(np.int32),
            (items * rng.random(nnz) ** 1.6).astype(np.int32),
            (rng.random(nnz) * 5).astype(np.float32), users, items,
        )
        rep = als_train(coo, rank=8, iterations=2, lam=0.05, seed=1,
                        layout="fused", matmul_dtype="float32")
        tp = als_train(coo, rank=8, iterations=2, lam=0.05, seed=1,
                       mesh=mesh, layout="fused", shard_factors=True,
                       matmul_dtype="float32")
        np.testing.assert_allclose(np.asarray(rep.user),
                                   np.asarray(tp.user),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(rep.item),
                                   np.asarray(tp.item),
                                   rtol=2e-4, atol=2e-4)
        assert tp.item.sharding.spec[0] == "model"


class TestServingDispatch:
    def test_sharded_model_serves_equal_to_brute(self, tmp_path):
        """save() persists the sharded fact; a plain load() restores
        row-sharded and recommend()/batch_topk() dispatch through the
        distributed merge with results equal to the replicated brute
        path — the deploy acceptance pin."""
        import os

        from predictionio_tpu.models.als import ALSModel
        from predictionio_tpu.utils.bimap import BiMap, EntityIdIxMap

        if len(jax.devices()) < 8:
            pytest.skip("needs the 8-device virtual mesh")
        rng = np.random.default_rng(21)
        U, I, K = 40, 64, 8
        model = ALSModel(
            rank=K,
            user_factors=jnp.asarray(
                rng.standard_normal((U, K)).astype(np.float32)),
            item_factors=jnp.asarray(
                rng.standard_normal((I, K)).astype(np.float32)),
            user_ids=EntityIdIxMap(
                BiMap({f"u{i}": i for i in range(U)})),
            item_ids=EntityIdIxMap(
                BiMap({f"i{i}": i for i in range(I)})),
            seen_by_user={0: np.asarray([1, 2, 3], dtype=np.int32)},
        )
        d = str(tmp_path / "model")
        env = {"PIO_SERVING_ANN_BUILD": "0"}
        old = {k: os.environ.get(k) for k in
               ("PIO_SERVING_ANN_BUILD", "PIO_SERVING_SHARD_FACTORS")}
        os.environ.update(env)
        try:
            model.save(d)
            os.environ["PIO_SERVING_SHARD_FACTORS"] = "1"
            sharded = ALSModel.load(d)
            os.environ["PIO_SERVING_SHARD_FACTORS"] = "0"
            brute = ALSModel.load(d)
        finally:
            for k, v in old.items():
                os.environ.pop(k, None)
                if v is not None:
                    os.environ[k] = v
        assert sharded.factor_shard_ways == 8
        assert brute.factor_shard_ways == 1
        for uid in ("u0", "u5", "u11"):
            a = brute.recommend(uid, 10)
            b = sharded.recommend(uid, 10)
            assert [x[0] for x in a] == [x[0] for x in b]
            assert np.allclose([x[1] for x in a], [x[1] for x in b],
                               atol=1e-5)
        uixs = np.asarray([0, 5, 11], dtype=np.int32)
        cols = np.zeros((3, 512), dtype=np.int32)
        mask = np.zeros((3, 512), dtype=np.float32)
        cols[0, :3] = [1, 2, 3]
        mask[0, :3] = 1.0
        va, ia = brute.batch_topk(uixs, cols, mask, None, 12)
        vb, ib = sharded.batch_topk(uixs, cols, mask, None, 12)
        np.testing.assert_array_equal(np.asarray(ia), np.asarray(ib))
        np.testing.assert_allclose(np.asarray(va), np.asarray(vb),
                                   atol=1e-5)

    @pytest.mark.parametrize("user_table", ["replicated", "sharded"])
    @pytest.mark.parametrize("B", [1, 3, 8])
    def test_batch_topk_sharded_equals_vectors_through_the_merge(
            self, B, user_table):
        """The deployed-sharded branch gets its query vectors from one
        jitted gather (``models/als._take_rows``), whether the user
        table is row-sharded too or not: the same answer as the
        distributed merge on ``user_factors[uixs]`` and the model's
        bfloat16 serving copy of the item table, row-sharded like the
        table it was cast from."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from predictionio_tpu.models.als import ALSModel
        from predictionio_tpu.utils.bimap import EntityIdIxMap

        mesh = _mesh((1, 8))
        rng = np.random.default_rng(50 + B)
        U, I, K = 40, 64, 8
        rows = NamedSharding(mesh, P("model", None))
        users = jnp.asarray(rng.standard_normal((U, K)).astype(np.float32))
        model = ALSModel(
            rank=K,
            user_factors=(jax.device_put(users, rows)
                          if user_table == "sharded" else users),
            item_factors=jax.device_put(
                rng.standard_normal((I, K)).astype(np.float32), rows),
            user_ids=EntityIdIxMap.from_ids([f"u{i}" for i in range(U)]),
            item_ids=EntityIdIxMap.from_ids([f"i{i}" for i in range(I)]),
            seen_by_user={})
        assert model.factor_shard_ways == 8
        uixs = rng.integers(0, U, B).astype(np.int32)
        cols = rng.integers(0, I, (B, 8)).astype(np.int32)
        mask = (rng.random((B, 8)) < 0.5).astype(np.float32)
        got = model.batch_topk(uixs, cols, mask, None, 12)
        served = model.serving_item_factors()
        assert served.dtype == jnp.bfloat16
        assert served.sharding == model.item_factors.sharding
        want = recommend_topk_sharded(
            users[uixs], served, jnp.asarray(cols),
            jnp.asarray(mask), jnp.ones((I,), jnp.float32), 12, mesh)
        np.testing.assert_array_equal(np.asarray(got[1]),
                                      np.asarray(want[1]))
        np.testing.assert_array_equal(np.asarray(got[0]),
                                      np.asarray(want[0]))

    def test_env_resolution(self, monkeypatch):
        """PIO_TRAIN_SHARD_FACTORS: 1 forces on, 0 forces off, unset
        defers to the engine param — resolve_shard_factors is the one
        routing point every ALS template goes through."""
        from predictionio_tpu.ops.als import resolve_shard_factors

        monkeypatch.delenv("PIO_TRAIN_SHARD_FACTORS", raising=False)
        assert resolve_shard_factors(True) is True
        assert resolve_shard_factors(False) is False
        monkeypatch.setenv("PIO_TRAIN_SHARD_FACTORS", "1")
        assert resolve_shard_factors(False) is True
        monkeypatch.setenv("PIO_TRAIN_SHARD_FACTORS", "off")
        assert resolve_shard_factors(True) is False


class TestMeshChild:
    def test_forced_8_device_child_pins_parity_and_serving(
            self, run_mesh_child):
        """Fresh-process proof: env-driven sharded training matches
        replicated on every mesh shape AND a persisted-sharded model
        round-trips into sharded serving — under XLA_FLAGS the child
        sets itself, independent of this process's topology."""
        code, out, err = run_mesh_child(
            "mesh_parity_child.py",
            env={"PIO_TRAIN_SHARD_FACTORS": "1"})
        assert code == 0, f"child failed\nstdout:\n{out}\nstderr:\n{err}"
        assert "MESH PARITY OK" in out, out
        for shape in ("1x8", "2x4", "4x2"):
            assert f"parity {shape}: OK" in out, out
