"""Sublinear retrieval (ops/ann): IVF-flat MIPS index + exact rescore.

Four layers, matching the serving stack:

- build/geometry: the k-means coarse quantizer's membership tables
  (every item in exactly one cell, capacity-bounded lists, auto sizing);
- quality parity: seeded synthetic-factor harness — recall@shortlist
  >= 0.95 and MAP@10 within 1% of brute force at the default nprobe,
  recall monotone in nprobe, and EXACT equality to brute when every
  cell is probed (the rescore-is-exact invariant);
- model integration: ALSModel dispatches recommend/similar/batch_topk
  through the index when configured, masks seen/disallowed items on
  the shortlist, and round-trips the index through the checksummed
  checkpoint envelope (corruption raises CheckpointCorruptError);
- serving e2e: `pio deploy --retrieval ann` semantics — /stats.json
  annEnabled + shortlist histogram, pio_serving_ann_* on /metrics,
  /reload swaps atomically (cache generation bumped on success,
  last-known-good index keeps serving on a torn checkpoint).
"""

import json
import urllib.error
import urllib.request

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from predictionio_tpu.ops import ann as ann_ops

pytestmark = pytest.mark.ann

K = 16


def _factors(n, n_clusters=64, seed=0, k=K):
    """Mixture-of-gaussians vectors — the clustered shape real ALS
    factor tables have (taste clusters), which is what IVF exploits."""
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(n_clusters, k)).astype(np.float32) * 2.0
    asg = rng.integers(0, n_clusters, size=n)
    noise = rng.normal(size=(n, k)).astype(np.float32) * 0.5
    return (centers[asg] + noise).astype(np.float32)


# ---------------------------------------------------------------------------
# build / geometry
# ---------------------------------------------------------------------------


class TestBuild:
    def test_below_min_items_returns_none(self):
        assert ann_ops.build_index(_factors(256)) is None

    def test_membership_partition_and_caps(self):
        n = 4096
        idx = ann_ops.build_index(_factors(n), seed=0)
        assert idx is not None and idx.n_items == n
        # every item in exactly one cell: flat_items is a permutation
        assert sorted(idx.flat_items.tolist()) == list(range(n))
        # CSR offsets cover the catalog exactly, monotonically
        assert idx.cell_offset[0] == 0 and idx.cell_offset[-1] == n
        sizes = np.diff(idx.cell_offset)
        assert (sizes >= 0).all()
        # balanced assignment: no cell beyond balance * mean
        assert sizes.max() <= np.ceil(2.0 * n / idx.nlist)
        assert idx.max_cell == sizes.max()
        # the vector copy is the factor rows in flat order (rescore
        # reads these — exactness depends on the copy being exact)
        np.testing.assert_array_equal(idx.flat_vecs,
                                      _factors(n)[idx.flat_items])

    def test_auto_sizing_bounds(self):
        assert ann_ops.auto_nlist(0) == 8
        # 4*sqrt(n) band, capped so the mean cell keeps >=128 members
        assert ann_ops.auto_nlist(100_000) == 512
        assert ann_ops.auto_nlist(1_000_000) == 4096
        assert ann_ops.auto_nlist(10**9) <= 4096
        nlist = ann_ops.auto_nlist(4096)
        assert ann_ops.auto_nprobe(nlist) >= 1

    def test_explicit_nlist_respected(self):
        idx = ann_ops.build_index(_factors(2048), nlist=32, seed=1)
        assert idx.nlist == 32

    def test_oversized_nlist_clamps_to_sample(self):
        """An explicit nlist beyond the k-means training sample clamps
        (degrade-don't-die) instead of crashing the persist stage."""
        idx = ann_ops.build_index(_factors(2048), nlist=1024, seed=1,
                                  sample=512)
        assert idx is not None and idx.nlist == 512

    def test_build_deterministic_for_seed(self):
        a = ann_ops.build_index(_factors(2048), seed=3)
        b = ann_ops.build_index(_factors(2048), seed=3)
        np.testing.assert_array_equal(a.centroids, b.centroids)
        np.testing.assert_array_equal(a.flat_items, b.flat_items)


# ---------------------------------------------------------------------------
# quality parity vs brute force
# ---------------------------------------------------------------------------


class TestQualityParity:
    def test_recall_and_map_at_default_nprobe(self):
        # 16384 items is the smallest catalog where the auto-nprobe
        # probe FRACTION matches the large-catalog regime the index is
        # for (at 4096 the same default probes a thinner slice of the
        # clusters and lands ~0.97 — see the monotonicity test for that
        # regime)
        items = _factors(16384, seed=0)
        users = _factors(128, seed=1)
        idx = ann_ops.build_index(items, seed=0)
        q = ann_ops.quality_vs_brute(idx, users, items, k=10)
        assert q["recall_at_shortlist"] >= 0.95, q
        # brute MAP@10 against itself is 1.0 by construction, so
        # "within 1% of brute" reads directly as >= 0.99
        assert q["map_at_k"] >= 0.99, q

    def test_recall_monotone_in_nprobe(self):
        items = _factors(4096, seed=2)
        users = _factors(96, seed=3)
        idx = ann_ops.build_index(items, seed=0)
        recalls = [
            ann_ops.quality_vs_brute(idx, users, items, k=10,
                                     nprobe=p)["recall_at_shortlist"]
            for p in (2, 8, 32, idx.nlist)
        ]
        assert recalls == sorted(recalls), recalls
        assert recalls[-1] == 1.0  # full probe reaches everything

    def test_full_probe_equals_brute_exactly(self):
        """Probing every cell makes the shortlist the whole catalog —
        the ranking must then be IDENTICAL to brute force (rescore is
        exact, not approximate)."""
        from predictionio_tpu.ops import topk as topk_ops

        items = _factors(2048, seed=4)
        users = _factors(32, seed=5)
        idx = ann_ops.build_index(items, seed=0)
        uv, itf = jnp.asarray(users), jnp.asarray(items)
        b = users.shape[0]
        no_cols = jnp.zeros((b, 1), dtype=jnp.int32)
        no_mask = jnp.zeros((b, 1), dtype=jnp.float32)
        allow = jnp.ones((items.shape[0],), dtype=jnp.float32)
        bv, bi = topk_ops.recommend_topk(uv, itf, no_cols, no_mask, allow, 10)
        c, fi, fv, co = idx.device_arrays()
        av, ai = ann_ops.ann_topk(uv, itf, c, fi, fv, co, no_cols, no_mask,
                                  allow, 10, idx.nlist)
        np.testing.assert_array_equal(np.asarray(ai), np.asarray(bi))
        np.testing.assert_allclose(np.asarray(av), np.asarray(bv), rtol=1e-5)

    def test_seen_and_disallowed_masked_on_shortlist(self):
        items = _factors(2048, seed=6)
        users = _factors(16, seed=7)
        idx = ann_ops.build_index(items, seed=0)
        uv, itf = jnp.asarray(users), jnp.asarray(items)
        b = users.shape[0]
        rng = np.random.default_rng(8)
        seen = rng.integers(0, 2048, (b, 8)).astype(np.int32)
        allow = np.ones((2048,), dtype=np.float32)
        deny = rng.integers(0, 2048, 64)
        allow[deny] = 0.0
        c, fi, fv, co = idx.device_arrays()
        vals, idxs = ann_ops.ann_topk(
            uv, itf, c, fi, fv, co, jnp.asarray(seen),
            jnp.ones((b, 8), dtype=jnp.float32), jnp.asarray(allow),
            10, idx.nlist)
        vals, idxs = np.asarray(vals), np.asarray(idxs)
        finite = np.isfinite(vals)
        for row in range(b):
            got = set(idxs[row][finite[row]].tolist())
            assert not got & set(seen[row].tolist())
            assert not got & set(deny.tolist())
        # non-finite slots carry out-of-range sentinels
        assert (idxs[~finite] >= 2048).all()

    def test_rescore_budget_truncates_statically(self):
        items = _factors(2048, seed=9)
        idx = ann_ops.build_index(items, seed=0)
        nprobe = idx.clamp_nprobe(0)
        full = idx.shortlist_width(nprobe)
        assert idx.shortlist_width(nprobe, rescore=128) == min(full, 128)
        uv = jnp.asarray(_factors(4, seed=10))
        c, fi, fv, co = idx.device_arrays()
        no_cols = jnp.zeros((4, 1), dtype=jnp.int32)
        no_mask = jnp.zeros((4, 1), dtype=jnp.float32)
        allow = jnp.ones((2048,), dtype=jnp.float32)
        vals, _ = ann_ops.ann_topk(uv, jnp.asarray(items), c, fi, fv, co,
                                   no_cols, no_mask, allow, 256, nprobe, 128)
        # k clamps to the rescore budget (the shortlist width)
        assert vals.shape == (4, 128)

    def test_similar_full_probe_matches_brute_cosine(self):
        from predictionio_tpu.ops import topk as topk_ops

        items = _factors(2048, seed=11)
        idx = ann_ops.build_index(items, seed=0)
        itf = jnp.asarray(items)
        qv = itf[:8]
        ex_cols = jnp.arange(8, dtype=jnp.int32)[:, None]
        ex_mask = jnp.ones((8, 1), dtype=jnp.float32)
        allow = jnp.ones((2048,), dtype=jnp.float32)
        bv, bi = topk_ops.similar_topk(qv, itf, ex_cols, ex_mask, allow, 10)
        c, fi, fv, co = idx.device_arrays()
        av, ai = ann_ops.ann_similar_topk(qv, itf, c, fi, fv, co, ex_cols,
                                          ex_mask, allow, 10, idx.nlist)
        np.testing.assert_array_equal(np.asarray(ai), np.asarray(bi))
        np.testing.assert_allclose(np.asarray(av), np.asarray(bv),
                                   rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# Online delta overlay x ANN (PR 14 satellite): delta/cold-start items
# are brute-scored on the host and merged with the IVF shortlist — the
# index is never rebuilt online, so retrieval for unchanged items must
# stay bit-identical (docs/serving-performance.md has the
# overlay-size-vs-latency tradeoff)
# ---------------------------------------------------------------------------


@pytest.mark.online
class TestOnlineOverlayNeutrality:
    def _overlay(self, model, items=None, users=None):
        from predictionio_tpu.online.overlay import (
            ItemDelta,
            OnlineOverlay,
            UserDelta,
        )

        overlay = OnlineOverlay(generation=0)
        for iid, vec in (items or {}).items():
            assert overlay.put_item(iid, ItemDelta(vector=vec),
                                    generation=0)
        for uid, delta in (users or {}).items():
            assert overlay.put_user(uid, delta, generation=0)
        model.set_online_overlay(overlay)
        return overlay

    def test_unchanged_items_rank_identically_under_overlay(self):
        """Recall-neutrality: with overlay ITEMS present, the base-
        catalog portion of an ANN answer is exactly the no-overlay ANN
        answer — the overlay merge may only INSERT delta items, never
        reorder or drop catalog items."""
        m = _als_model(seed=31)
        m.configure_retrieval("ann")
        baseline = m.recommend("u1", 10)
        # a delta item with a tiny vector: scores ~0, never competitive
        cold = np.full((K,), 1e-6, dtype=np.float32)
        self._overlay(m, items={"fresh1": cold})
        with_overlay = m.recommend("u1", 10)
        catalog_part = [r for r in with_overlay if r[0] != "fresh1"]
        assert [r[0] for r in catalog_part[:len(baseline) - 1]] == \
            [r[0] for r in baseline[:len(baseline) - 1]]
        for (got_id, got_s), (want_id, want_s) in zip(catalog_part,
                                                      baseline):
            assert got_id == want_id
            assert got_s == pytest.approx(want_s, rel=1e-5)

    def test_competitive_delta_item_merges_into_topk(self):
        m = _als_model(seed=32)
        m.configure_retrieval("ann")
        uix = m.user_ids.get("u2")
        uv = np.asarray(m.user_factors[uix])
        # a delta item aligned with the user's taste: must win rank 1
        self._overlay(m, items={"hot": (uv * 10.0).astype(np.float32)})
        recs = m.recommend("u2", 10)
        assert recs[0][0] == "hot"
        # and the catalog items that follow are the baseline ones
        m.set_online_overlay(None)
        baseline = m.recommend("u2", 10)
        assert [r[0] for r in recs[1:]] == \
            [r[0] for r in baseline[:len(recs) - 1]]

    def test_filtered_queries_serve_catalog_only(self):
        """Business-rule-filtered queries (allow vector present) skip
        the overlay merge — the allow vector is indexed by catalog
        position and cannot vouch for overlay items (documented
        caveat, docs/freshness.md)."""
        m = _als_model(seed=33)
        m.configure_retrieval("ann")
        uix = m.user_ids.get("u3")
        uv = np.asarray(m.user_factors[uix])
        self._overlay(m, items={"hot": (uv * 10.0).astype(np.float32)})
        allow = np.ones((m.item_factors.shape[0],), dtype=np.float32)
        recs = m.recommend("u3", 10, allow=allow)
        assert all(r[0] != "hot" for r in recs)

    def test_folded_user_vector_drives_ann_ranking(self):
        """A folded user's ANN answer equals the answer the BASE path
        would give for that exact vector — the overlay changes the
        query vector, never the retrieval behavior."""
        from predictionio_tpu.online.overlay import UserDelta

        m = _als_model(seed=34)
        m.configure_retrieval("ann")
        donor = m.recommend("u4", 10)
        vec = np.asarray(m.user_factors[m.user_ids.get("u4")])
        self._overlay(m, users={
            "brand-new": UserDelta(vector=vec.astype(np.float32))})
        folded = m.recommend("brand-new", 10)
        assert [r[0] for r in folded] == [r[0] for r in donor]

    def test_delta_seen_items_are_excluded_for_their_user(self):
        from predictionio_tpu.online.overlay import UserDelta

        m = _als_model(seed=35)
        uix = m.user_ids.get("u5")
        uv = np.asarray(m.user_factors[uix]).astype(np.float32)
        hot = (uv * 10.0).astype(np.float32)
        self._overlay(
            m, items={"hot": hot},
            users={"u5": UserDelta(vector=uv, delta_seen=("hot",))})
        # u5 already interacted with "hot": excluded for them...
        assert all(r[0] != "hot" for r in m.recommend("u5", 10))
        # ...but still recommendable to a taste-adjacent other user
        m6 = m.recommend("u5", 10, exclude_seen=False)
        assert m6[0][0] == "hot"


# ---------------------------------------------------------------------------
# ALSModel integration + persistence
# ---------------------------------------------------------------------------


def _als_model(n_items=2048, n_users=32, seed=0):
    from predictionio_tpu.models.als import ALSModel
    from predictionio_tpu.utils.bimap import EntityIdIxMap

    items = _factors(n_items, seed=seed)
    users = _factors(n_users, seed=seed + 1)
    return ALSModel(
        rank=K,
        user_factors=jnp.asarray(users),
        item_factors=jnp.asarray(items),
        user_ids=EntityIdIxMap.from_ids([f"u{i}" for i in range(n_users)]),
        item_ids=EntityIdIxMap.from_ids([f"i{i}" for i in range(n_items)]),
        seen_by_user={0: np.asarray([3, 4, 5], dtype=np.int32)},
    )


class TestModelIntegration:
    def test_configure_retrieval_builds_and_dispatches(self):
        m = _als_model()
        widths = []
        m.configure_retrieval("ann",
                              observer=lambda w, q: widths.append((w, q)))
        assert m.ann_enabled and m.ann_index is not None
        recs = m.recommend("u0", 5)
        assert len(recs) == 5
        assert widths and widths[0][1] == 1
        # seen items stay excluded through the ANN path
        names = {r[0] for r in recs}
        assert not names & {"i3", "i4", "i5"}

    def test_full_probe_recommend_matches_brute_path(self):
        """Probing every cell, ANN's exact float32 rescore ranks like
        brute force over the float32 table. The served brute path
        scores from the model's bfloat16 copy: the same items up to
        scores that tie within 2^-8 |u| |v|."""
        from predictionio_tpu.ops import topk as topk_ops

        m = _als_model(seed=20)
        brute = m.recommend("u1", 10)
        m.configure_retrieval("ann")
        m.ann_nprobe = m.ann_index.nlist        # probe everything
        ann = m.recommend("u1", 10)
        _, exact = topk_ops.recommend_topk(
            m.user_factors[1:2], m.item_factors,
            jnp.zeros((1, 1), jnp.int32), jnp.zeros((1, 1), jnp.float32),
            jnp.ones((m.item_factors.shape[0],), jnp.float32), 10)
        assert [r[0] for r in ann] == [f"i{i}" for i in np.asarray(exact)[0]]
        u = np.asarray(m.user_factors[1])
        items = np.asarray(m.item_factors)
        for name, score in brute:
            v = items[int(name[1:])]
            assert abs(score - float(u @ v)) <= (
                2.0 ** -8 * np.linalg.norm(u) * np.linalg.norm(v))
        assert len({r[0] for r in brute} & {r[0] for r in ann}) >= 9

    def test_full_probe_similar_matches_brute_path(self):
        m = _als_model(seed=21)
        brute = m.similar(["i0", "i1"], 10)
        m.configure_retrieval("ann")
        m.ann_nprobe = m.ann_index.nlist
        ann = m.similar(["i0", "i1"], 10)
        assert [r[0] for r in ann] == [r[0] for r in brute]

    def test_batch_topk_dispatches_ann(self):
        m = _als_model(seed=22)
        calls = []
        m.configure_retrieval("ann",
                              observer=lambda w, q: calls.append((w, q)))
        cols = np.zeros((4, 8), dtype=np.int32)
        mask = np.zeros((4, 8), dtype=np.float32)
        vals, idxs = m.batch_topk(np.arange(4, dtype=np.int32), cols, mask,
                                  None, 10)
        assert np.asarray(vals).shape[0] == 4
        assert calls == [(m.ann_index.shortlist_width(
            m.ann_index.clamp_nprobe(0)), 4)]

    @pytest.mark.parametrize("B", [1, 4, 16])
    def test_batch_topk_ann_equals_vectors_through_ann_topk(self, B):
        """The ANN branch gets its query vectors from one jitted gather
        (``models/als._take_rows``): the same answer as the kernel on
        ``user_factors[uixs]``."""
        m = _als_model(seed=26)
        m.configure_retrieval("ann")
        rng = np.random.default_rng(B)
        uixs = rng.integers(0, 32, B).astype(np.int32)
        cols = rng.integers(0, 2048, (B, 8)).astype(np.int32)
        mask = (rng.random((B, 8)) < 0.5).astype(np.float32)
        got = m.batch_topk(uixs, cols, mask, None, 10)
        centroids, flat_items, flat_vecs, cell_offset, nprobe, rescore = \
            m._ann_args()
        want = ann_ops.ann_topk(
            m.user_factors[uixs], m.item_factors, centroids, flat_items,
            flat_vecs, cell_offset, jnp.asarray(cols), jnp.asarray(mask),
            m._allow_or_default(None), 10, nprobe, rescore)
        np.testing.assert_array_equal(np.asarray(got[1]),
                                      np.asarray(want[1]))
        np.testing.assert_array_equal(np.asarray(got[0]),
                                      np.asarray(want[0]))

    def test_small_catalog_degrades_to_brute(self, caplog):
        m = _als_model(n_items=128)
        m.configure_retrieval("ann")
        assert not m.ann_enabled and m.retrieval == "brute"
        assert m.recommend("u0", 5)  # still serves

    def test_save_builds_and_load_round_trips(self, tmp_path):
        from predictionio_tpu.models.als import ALSModel

        m = _als_model(seed=23)
        assert m.ann_index is None
        m.save(str(tmp_path))
        assert m.ann_index is not None       # built at persist time
        loaded = ALSModel.load(str(tmp_path))
        assert loaded.ann_index is not None
        np.testing.assert_array_equal(loaded.ann_index.centroids,
                                      m.ann_index.centroids)
        np.testing.assert_array_equal(loaded.ann_index.flat_items,
                                      m.ann_index.flat_items)
        assert loaded.ann_index.n_items == m.ann_index.n_items
        # loaded model serves through the loaded index
        loaded.configure_retrieval("ann")
        assert loaded.ann_enabled and loaded.recommend("u0", 5)

    def test_small_catalog_save_skips_index(self, tmp_path):
        from predictionio_tpu.models.als import ALSModel

        m = _als_model(n_items=128)
        m.save(str(tmp_path))
        loaded = ALSModel.load(str(tmp_path))
        assert loaded.ann_index is None

    def test_env_opt_out_skips_persist_build(self, tmp_path, monkeypatch):
        """PIO_SERVING_ANN_BUILD=0: brute-only fleets skip the k-means
        build and the checkpoint's second copy of the item table."""
        from predictionio_tpu.models.als import ALSModel

        monkeypatch.setenv("PIO_SERVING_ANN_BUILD", "0")
        m = _als_model(seed=25)
        m.save(str(tmp_path))
        assert m.ann_index is None
        assert ALSModel.load(str(tmp_path)).ann_index is None

    def test_corrupt_ann_payload_raises_checkpoint_error(
            self, tmp_path, monkeypatch):
        """A bit-flipped ANN payload fails the envelope checksum at
        load — never a silently wrong (or silently brute) deployment."""
        from predictionio_tpu.models.als import ALSModel
        from predictionio_tpu.utils import checkpoint as ckpt

        # the npz backend is the one with host-local bytes to checksum
        monkeypatch.setattr(ckpt, "_ocp", lambda: None)
        m = _als_model(seed=24)
        m.save(str(tmp_path))
        payload = next((tmp_path / "ann").glob("arrays-*.npz"))
        blob = bytearray(payload.read_bytes())
        blob[len(blob) // 2] ^= 0x20
        payload.write_bytes(bytes(blob))
        with pytest.raises(ckpt.CheckpointCorruptError):
            ALSModel.load(str(tmp_path))


# ---------------------------------------------------------------------------
# serving e2e: deploy --retrieval ann, /stats.json, /metrics, /reload
# ---------------------------------------------------------------------------

N_USERS, N_ITEMS = 12, 16

REC_VARIANT = {
    "id": "rec-ann",
    "engineFactory":
        "predictionio_tpu.templates.recommendation.engine_factory",
    "datasource": {"params": {"app_name": "AnnApp"}},
    "algorithms": [
        {"name": "als",
         "params": {"rank": 8, "num_iterations": 6, "lambda_": 0.05,
                    "seed": 1}}
    ],
}


@pytest.fixture
def rec_storage(storage):
    from predictionio_tpu.core.datamap import DataMap
    from predictionio_tpu.core.event import Event
    from predictionio_tpu.storage.base import App

    app_id = storage.get_meta_data_apps().insert(App(0, "AnnApp"))
    events = storage.get_events()
    events.init(app_id)
    rng = np.random.default_rng(0)
    for u in range(N_USERS):
        for i in range(N_ITEMS):
            if i % 2 == u % 2 and rng.random() < 0.8:
                events.insert(Event(
                    event="rate", entity_type="user", entity_id=f"u{u}",
                    target_entity_type="item", target_entity_id=f"i{i}",
                    properties=DataMap({"rating": 5.0})), app_id)
    return storage


def _post_json(url, payload):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=10) as r:
        return r.status, json.loads(r.read())


@pytest.mark.ann
class TestServingE2E:
    def _deploy(self, rec_storage, monkeypatch, tmp_path):
        """Train with a small catalog indexed anyway (MIN_INDEX_ITEMS
        lowered), then serve it with retrieval=ann."""
        from predictionio_tpu.api.engine_server import create_engine_server
        from predictionio_tpu.workflow.deploy import ServerConfig
        from predictionio_tpu.workflow.train import run_train

        monkeypatch.setenv("PIO_MODEL_DIR", str(tmp_path))
        monkeypatch.setattr(ann_ops, "MIN_INDEX_ITEMS", 8)
        outcome = run_train(variant=REC_VARIANT, storage=rec_storage)
        assert outcome.status == "COMPLETED"
        server = create_engine_server(
            storage=rec_storage,
            config=ServerConfig(ip="127.0.0.1", port=0, retrieval="ann",
                                cache_enabled=True))
        server.start()
        return server

    def test_ann_serving_stats_metrics_and_reload(
            self, rec_storage, monkeypatch, tmp_path):
        server = self._deploy(rec_storage, monkeypatch, tmp_path)
        try:
            base = f"http://127.0.0.1:{server.port}"
            status, r = _post_json(f"{base}/queries.json",
                                   {"user": "u0", "num": 5})
            assert status == 200 and r["itemScores"]

            with urllib.request.urlopen(f"{base}/stats.json",
                                        timeout=10) as resp:
                doc = json.loads(resp.read())
            assert doc["annEnabled"] is True
            assert doc["retrieval"] == "ann"
            assert doc["serving"]["annQueries"] >= 1
            assert doc["serving"]["annShortlistHistogram"]

            with urllib.request.urlopen(f"{base}/metrics",
                                        timeout=10) as resp:
                text = resp.read().decode()
            assert "pio_serving_ann_enabled 1" in text
            assert "pio_serving_ann_shortlist_size" in text

            # successful /reload: cache generation bumped, ANN stays on,
            # and the re-wired observer keeps counting
            gen0 = server.service.cache.generation
            with urllib.request.urlopen(f"{base}/reload", timeout=30) as resp:
                assert resp.status == 200
            assert server.service.cache.generation == gen0 + 1
            assert server.service.ann_enabled()
            before = server.service.serving_stats.count("ann_queries")
            status, r = _post_json(f"{base}/queries.json",
                                   {"user": "u1", "num": 5})
            assert status == 200 and r["itemScores"]
            assert server.service.serving_stats.count("ann_queries") > before
        finally:
            server.stop()

    def test_reload_over_torn_ann_checkpoint_keeps_last_known_good(
            self, rec_storage, monkeypatch, tmp_path):
        import shutil

        server = self._deploy(rec_storage, monkeypatch, tmp_path)
        try:
            base = f"http://127.0.0.1:{server.port}"
            status, r = _post_json(f"{base}/queries.json",
                                   {"user": "u0", "num": 5})
            assert status == 200 and r["itemScores"]
            gen0 = server.service.cache.generation

            # tear the persisted ANN checkpoint: meta still names the
            # index, payload is gone -> load fails loudly
            ann_dirs = list(tmp_path.rglob("ann"))
            assert ann_dirs, "persisted model should carry an ann/ subdir"
            for d in ann_dirs:
                shutil.rmtree(d)
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(f"{base}/reload", timeout=30)
            assert e.value.code == 503
            assert "still serving" in json.loads(e.value.read())["message"]

            # last-known-good index still answers, cache generation
            # untouched (the warm cache survives a FAILED reload)
            assert server.service.ann_enabled()
            assert server.service.cache.generation == gen0
            status, r = _post_json(f"{base}/queries.json",
                                   {"user": "u0", "num": 5})
            assert status == 200 and r["itemScores"]
        finally:
            server.stop()
