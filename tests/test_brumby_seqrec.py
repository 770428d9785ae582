"""The ``brumby`` block of the session engine at a tiny size on the CPU:
power retention against the plain reference's quadratic form and the
state equations, the model through ``batch_predict`` and the engine
server against ``benchmarks/reference/brumby_jnp.py``, the bfloat16
device tree and the token budget."""

from __future__ import annotations

import gc
import json
import urllib.request
from datetime import datetime, timedelta, timezone

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import brumby_jnp as ref
from predictionio_tpu.models import seqrec
from predictionio_tpu.ops import qk_norm, retention
from predictionio_tpu.templates import sessionrec
from predictionio_tpu.utils.bimap import BiMap
from tests import retention_cases as cases

ITEMS, S = 500, 64
#: the tiny preset: d 64, 4 and 2 heads of 16, SwiGLU 128, 2 layers
PARAMS = dict(backbone="brumby", d_model=64, n_heads=4, n_kv_heads=2,
              head_dim=16, d_ff=128, n_layers=2, max_len=S,
              tie_embeddings=False, param_dtype="bfloat16", use_mesh=False)
REF_CONFIG = {"num_attention_heads": 4, "num_key_value_heads": 2,
              "head_dim": 16, "rms_norm_eps": 1e-6, "rope_theta": 1e6,
              "gate_init_logit": 6.906768, "retention_eps": 1e-6}
#: logits of the tiny model against the reference: bfloat16 activations
#: through two layers (logits are ~N(0, 1); the worst seen is 0.03)
LOGIT_TOL = 0.06


def _qkvg(seed, S=S, H=4, G=2, d=16, gate_logit=6.9):
    """Inputs as the model hands them over: values that bfloat16 holds
    exactly (so operand rounding is not part of the comparison)."""
    rng = np.random.default_rng(seed)

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16
                           ).astype(jnp.float32)

    lg = jax.nn.log_sigmoid(jnp.asarray(
        gate_logit + rng.standard_normal((1, S, G)), jnp.float32))
    return draw(1, S, H, d), draw(1, S, G, d), draw(1, S, G, d), lg


def _rel(got, want):
    return float(jnp.max(jnp.abs(got - want))
                 / jnp.sqrt(jnp.mean(want * want)))


@pytest.mark.parametrize("d", [16, 32])          # one block of phi, two
@pytest.mark.parametrize("chunk", [16, 8, 64, None])
def test_chunked_retention_matches_the_quadratic_form(chunk, d):
    q, k, v, lg = _qkvg(0, d=d)
    want = ref.retention_quadratic(q[0], k[0], v[0], lg[0])
    got = retention.power_retention(q, k, v, lg, chunk=chunk)[0]
    # bfloat16 weights and features inside, float32 sums: 8 bits
    assert _rel(got, want) < 0.05


def test_chunk_and_feature_width_come_from_the_shape():
    assert retention.pick_chunk(64) == 64 and retention.pick_chunk(100) == 64
    assert retention.pick_chunk(16384) == 256
    # blocks of 16: 16 * (128 + 112 + ... + 16); one whole square at d=16
    assert retention.phi_width(128) == 9216 and retention.phi_width(16) == 256
    with pytest.raises(NotImplementedError):
        retention.power_retention(*_qkvg(0), degree=4)


def test_state_equations_match_the_quadratic_form():
    """S_t = g_t S_{t-1} + phi(k_t) v_t^T, z_t = g_t z_{t-1} + phi(k_t),
    y_t = phi(q_t)^T S_t / (phi(q_t)^T z_t + eps), with the explicit
    d(d+1)/2 monomials of u / d^(1/4), off-diagonal ones times sqrt 2."""
    q, k, v, lg = (np.asarray(a[0], np.float64) for a in _qkvg(1, S=24))
    d = q.shape[-1]
    iu = np.triu_indices(d)
    coef = np.where(iu[0] == iu[1], 1.0, np.sqrt(2.0))

    def phi(u):
        u = u / d ** 0.25
        return u[iu[0]] * u[iu[1]] * coef

    assert len(coef) == d * (d + 1) // 2
    H, G = q.shape[1], k.shape[1]
    got = np.zeros_like(q)
    for a in range(H):
        b = a // (H // G)
        state = np.zeros((len(coef), d))
        z = np.zeros(len(coef))
        for t in range(q.shape[0]):
            g = np.exp(lg[t, b])
            state = g * state + np.outer(phi(k[t, b]), v[t, b])
            z = g * z + phi(k[t, b])
            got[t, a] = phi(q[t, a]) @ state / (phi(q[t, a]) @ z + 1e-6)
    want = ref.retention_quadratic(*(jnp.asarray(a, jnp.float32)
                                     for a in (q, k, v, lg)))
    np.testing.assert_allclose(got, np.asarray(want), rtol=2e-4, atol=2e-5)


def test_gradients_match_autodiff_of_the_quadratic_form():
    q, k, v, lg = _qkvg(2, S=32)
    probe = jnp.asarray(np.random.default_rng(3).standard_normal(
        (32, 4, 16)), jnp.float32)

    def chunked(q, k, v, lg):
        return jnp.sum(retention.power_retention(q, k, v, lg, chunk=8)[0]
                       * probe)

    def quadratic(q, k, v, lg):
        return jnp.sum(ref.retention_quadratic(q[0], k[0], v[0], lg[0])
                       * probe)

    got = jax.grad(chunked, argnums=(0, 1, 2, 3))(q, k, v, lg)
    want = jax.grad(quadratic, argnums=(0, 1, 2, 3))(q, k, v, lg)
    for g, w in zip(got, want):
        assert bool(jnp.all(jnp.isfinite(g)))
        assert _rel(g, w) < 0.1


@pytest.mark.parametrize("name", list(cases.CASES))
def test_gradients_through_retention_are_the_parents(name):
    """``inference=False`` goes through the same layout functions, the
    step's cast and the way out's barrier: the gradient of ``sum(out *
    probe)`` by q, k, v and the gates, against the parent commit's
    (3ae3a70, on the CPU: the sum of absolute values of each; a sum's
    order may differ in its last bits)."""
    case = cases.CASES[name]
    q, k, v, log_g = cases.inputs(name, **case)
    probe = cases.probe(q.shape)

    def loss(q, k, v, log_g):
        out = retention.power_retention(
            q, k, v, log_g, degree=case["degree"], chunk=case["chunk"],
            inference=False)
        return jnp.sum(out.astype(jnp.float32) * probe)

    grads = jax.grad(loss, argnums=(0, 1, 2, 3))(q, k, v, log_g)
    for g, x, want in zip(grads, (q, k, v, log_g), cases.PARENT[name][1]):
        assert g.shape == x.shape and g.dtype == x.dtype
        assert bool(jnp.all(jnp.isfinite(g)))
        np.testing.assert_allclose(
            float(jnp.sum(jnp.abs(g.astype(jnp.float32)))), want, rtol=1e-5)


@pytest.mark.parametrize("R", [1, 2])
def test_the_way_in_rounds_where_the_model_did_and_carries_the_gradient(R):
    """Brumby's q and k through ``power_retention``'s way in — QK-norm
    rounded to the stream's type as ``_rms`` rounded it, RoPE, the cast
    — against the parent's formulation written out: the operands and
    the mixing bit for bit, the gradient by the projections' outputs
    and the norm's weights within float32 tolerance."""
    B, n, G, d, C = 2, 40, 2, 16, 16
    H = G * R
    rng = np.random.default_rng(R)
    q = jnp.asarray(rng.standard_normal((B, n, H * d)), jnp.bfloat16)
    k, v = (jnp.asarray(rng.standard_normal((B, n, G * d)), jnp.bfloat16)
            for _ in range(2))
    wq, wk = (jnp.asarray(1 + 0.1 * rng.standard_normal(d), jnp.float32)
              for _ in range(2))
    log_g = jnp.asarray(-np.abs(rng.standard_normal((B, n, G))) * 0.05,
                        jnp.float32)
    probe = cases.probe((B, n, H, d))
    how = dict(eps=1e-6, theta=1e6, norm_dtype=jnp.bfloat16)

    def new(q, k, wq, wk):
        way_in = retention.WayIn(wq, wk, 1e-6,
                                 qk_norm.rope_tables(n, d, 1e6), jnp.bfloat16)
        return retention.power_retention(
            q.reshape(B, n, H, d), k.reshape(B, n, G, d),
            v.reshape(B, n, G, d), log_g, chunk=C, way_in=way_in)

    def parents(q, k, wq, wk):
        return retention.power_retention(
            cases.plain_way_in(q, wq, heads=H, **how),
            cases.plain_way_in(k, wk, heads=G, **how),
            v.reshape(B, n, G, d), log_g, chunk=C)

    np.testing.assert_array_equal(np.asarray(new(q, k, wq, wk), np.float32),
                                  np.asarray(parents(q, k, wq, wk),
                                             np.float32))
    got, want = (jax.grad(
        lambda *a, f=f: jnp.sum(f(*a).astype(jnp.float32) * probe),
        argnums=(0, 1, 2, 3))(q, k, wq, wk) for f in (new, parents))
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32),
                                   rtol=1e-5, atol=1e-5)


def test_padding_after_the_last_event_changes_nothing():
    q, k, v, lg = _qkvg(4, S=40)
    short = retention.power_retention(q, k, v, lg, chunk=16)
    rng = np.random.default_rng(5)

    def pad(a, sign=1.0):
        junk = jnp.asarray(rng.standard_normal((1, 24) + a.shape[2:]),
                           a.dtype)
        return jnp.concatenate([a, sign * jnp.abs(junk)], axis=1)

    long = retention.power_retention(pad(q), pad(k), pad(v), pad(lg, -1.0),
                                     chunk=16)
    np.testing.assert_array_equal(np.asarray(short), np.asarray(long[:, :40]))


@pytest.mark.parametrize("seed", [0, 1])
def test_a_bfloat16_state_fails_the_tolerance(seed):
    """Where the state is long-lived (gates at one) and written many
    times (512 chunks of 4), summing it in bfloat16 loses the small
    additions; the float32 state stays at operand rounding."""
    rng = np.random.default_rng(seed)

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16
                           ).astype(jnp.float32)

    n = 2048
    q, k, v = draw(1, n, 2, 16), draw(1, n, 1, 16), draw(1, n, 1, 16)
    lg = jnp.full((1, n, 1), -1e-5, jnp.float32)
    want = ref.retention_quadratic(q[0], k[0], v[0], lg[0])[n // 2:]
    tol = 0.04
    kept = retention.power_retention(q, k, v, lg, chunk=4)[0, n // 2:]
    lost = retention.power_retention(q, k, v, lg, chunk=4,
                                     _state_dtype=jnp.bfloat16)[0, n // 2:]
    assert _rel(kept, want) < tol < 0.15 < _rel(lost, want)


# -- the model ---------------------------------------------------------------

@pytest.fixture(scope="module")
def engine_model():
    params = sessionrec.AlgorithmParams(**PARAMS)
    cfg = params.seqrec_config(vocab=ITEMS + 1)
    weights = jax.tree.map(np.array, seqrec.init_params(
        jax.random.PRNGKey(11), cfg))          # float32, as a trained one
    rng = np.random.default_rng(12)
    histories = {f"u{u}": rng.integers(1, ITEMS + 1, size=n).astype(np.int32)
                 for u, n in enumerate([S, S, 40, 7, 90, S, S, S, S, S])}
    model = sessionrec.SeqRecEngineModel(
        params=weights, cfg=cfg,
        item_index=BiMap({f"i{k}": k + 1 for k in range(ITEMS)}),
        histories=histories)
    return sessionrec.SeqRecAlgorithm(params), model


def _reference_logits(model, history):
    return np.asarray(ref.last_logits(
        sessionrec._as_device_tree(model), history[-S:], REF_CONFIG,
        row_block=16, mlp_block=32, vocab_block=200))


def _check_against_reference(model, user, item_scores, num):
    history = model.histories[user]
    logits = _reference_logits(model, history)
    assert len(item_scores) == num
    ids = [int(s["item"][1:]) + 1 for s in item_scores]
    assert not set(ids) & set(history[-S:].tolist()) and 0 not in ids
    for ix, s in zip(ids, item_scores):
        assert abs(s["score"] - logits[ix]) < LOGIT_TOL
    allowed = logits.copy()
    allowed[0] = -np.inf
    allowed[history[-S:]] = -np.inf
    tenth = np.sort(allowed)[-num]
    assert min(allowed[ids]) > tenth - 2 * LOGIT_TOL


def test_default_config_is_todays_model():
    cfg = seqrec.SeqRecConfig(vocab=11)
    assert cfg.block == "sasrec" and cfg.param_dtype == jnp.float32
    params = seqrec.init_params(jax.random.PRNGKey(0), cfg)
    assert set(params) == {"item_emb", "pos_emb", "out_ln", "layers"}
    assert params["item_emb"].dtype == jnp.float32
    with pytest.raises(ValueError, match="unknown block kind"):
        seqrec.init_params(jax.random.PRNGKey(0),
                           seqrec.SeqRecConfig(vocab=11, block="nope"))


def test_device_tree_is_bfloat16_with_no_float32_twin(engine_model):
    _, model = engine_model
    tree = sessionrec._as_device_tree(model)
    leaves = jax.tree.leaves(tree)
    assert leaves and all(a.dtype == jnp.bfloat16 for a in leaves)
    assert "head" in tree and "pos_emb" not in tree
    # the host copy stays what training left; nothing else is cached
    assert all(a.dtype == np.float32 for a in jax.tree.leaves(model.params))
    assert sessionrec._as_device_tree(model) is tree
    gc.collect()
    on_device = [a for a in jax.live_arrays()
                 if a.shape == tree["head"].shape]
    assert {a.dtype for a in on_device} == {jnp.dtype(jnp.bfloat16)}


@pytest.mark.parametrize("user", ["u0", "u2", "u3", "u4"])
def test_batch_predict_matches_the_reference(engine_model, user):
    """Full, short, very short and over-long (newest max_len kept)."""
    algo, model = engine_model
    queries = [(0, sessionrec.Query(user=user, num=10)),
               (1, sessionrec.Query(user="nobody", num=10))]
    got = dict(algo.batch_predict(model, queries))
    assert got[1].item_scores == ()
    scores = [{"item": s.item, "score": s.score} for s in got[0].item_scores]
    _check_against_reference(model, user, scores, 10)


def test_token_budget_splits_a_batch_and_counters_report_it(
        engine_model, monkeypatch):
    algo, model = engine_model
    seen = []
    model.set_dispatch_observer(lambda *a: seen.append(a))
    monkeypatch.setattr(sessionrec, "_device_memory_bytes", lambda: 16e9)
    assert sessionrec.token_budget(model) >= 256 * S     # the old cap rules
    users = [f"u{u}" for u in (0, 1, 5, 6, 7, 8, 9, 2)]
    queries = [(i, sessionrec.Query(user=u, num=5))
               for i, u in enumerate(users)]
    whole = dict(algo.batch_predict(model, queries))
    # room for 2 histories of 64 tokens beside the weights, no more
    per_token = seqrec.activation_bytes_per_token(model.cfg)
    monkeypatch.setattr(sessionrec, "_device_memory_bytes",
                        lambda: 4 * per_token * 2.5 * S)
    model.budget = 0                 # worked out once per model: again
    assert sessionrec.token_budget(model) == 2 * S
    split = dict(algo.batch_predict(model, queries))
    real = sum(min(len(model.histories[u]), S) for u in users)
    # one record a dispatch (sessionrec.SeqDispatch), always whole
    assert [(r.programs, r.tokens, r.padded_tokens, r.split)
            for (r,) in seen] == [(1, real, 8 * S, 0), (4, real, 8 * S, 1)]
    assert all(r.fused_retention_programs == 0 and r.moe_tokens == 0
               for (r,) in seen)
    for i in whole:
        assert [s.item for s in whole[i].item_scores] == \
            [s.item for s in split[i].item_scores]
        np.testing.assert_allclose(
            [s.score for s in whole[i].item_scores],
            [s.score for s in split[i].item_scores], atol=LOGIT_TOL)
    model.set_dispatch_observer(None)
    model.budget = 0


def test_engine_server_answers_match_the_reference(engine_model):
    import datetime as dt

    from predictionio_tpu.api.engine_server import EngineServer
    from predictionio_tpu.controller.base import FirstServing
    from predictionio_tpu.storage.base import EngineInstance
    from predictionio_tpu.workflow.deploy import DeployedEngine, ServerConfig

    algo, model = engine_model
    now = dt.datetime.now(dt.timezone.utc)
    instance = EngineInstance(
        id="t", status="COMPLETED", start_time=now, completion_time=now,
        engine_id="t", engine_version="1", engine_variant="t",
        engine_factory="t")
    server = EngineServer(
        DeployedEngine(None, instance, [algo], FirstServing(), [model]),
        ServerConfig(ip="127.0.0.1", port=0, batching=True, tracing=True))
    server.start()
    try:
        def post(body):
            req = urllib.request.Request(
                f"http://127.0.0.1:{server.port}/queries.json",
                data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=60) as resp:
                return json.loads(resp.read())

        answer = post({"user": "u1", "num": 10})
        _check_against_reference(model, "u1", answer["itemScores"], 10)
        assert post({"user": "nobody", "num": 10})["itemScores"] == []
        with urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/stats.json") as resp:
            serving = json.loads(resp.read())["serving"]
        assert serving["seqPrograms"] == 1 and serving["seqTokens"] == S
        assert serving["seqPaddedTokens"] == S
        assert serving["seqSplitDispatches"] == 0
        with urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/metrics") as resp:
            assert b"pio_serving_seq_programs_total 1" in resp.read()
        spans = {name for t in server.service.trace_log._ring
                 for name, *_ in t.spans()}
        assert {"dispatch.prepare", "dispatch.gather", "dispatch.enqueue",
                "dispatch.device_wait", "dispatch.fetch",
                "dispatch.results"} <= spans
    finally:
        server.stop()
        model.set_dispatch_observer(None)


def test_train_deploy_query_with_the_brumby_backbone(storage, monkeypatch,
                                                     tmp_path):
    """engine.json -> pio train -> model store -> pio deploy --batching
    -> /queries.json, as every engine is reached."""
    from predictionio_tpu.api.engine_server import create_engine_server
    from predictionio_tpu.core.event import Event
    from predictionio_tpu.storage.base import App
    from predictionio_tpu.workflow.deploy import ServerConfig
    from predictionio_tpu.workflow.train import run_train

    monkeypatch.setenv("PIO_MODEL_DIR", str(tmp_path))
    app_id = storage.get_meta_data_apps().insert(App(0, "BrumbyApp"))
    events = storage.get_events()
    events.init(app_id)
    t0 = datetime(2026, 1, 1, tzinfo=timezone.utc)
    rng = np.random.default_rng(0)
    for u in range(32):
        start = int(rng.integers(10))
        for t in range(8):
            events.insert(Event(
                event="view", entity_type="user", entity_id=f"u{u}",
                target_entity_type="item",
                target_entity_id=f"i{(start + t) % 10}",
                event_time=t0 + timedelta(minutes=u * 100 + t)), app_id)
    variant = {
        "id": "brumby-sess",
        "engineFactory": "predictionio_tpu.templates.sessionrec.engine_factory",
        "datasource": {"params": {"app_name": "BrumbyApp"}},
        "algorithms": [{"name": "seqrec", "params": {
            "backbone": "brumby", "d_model": 32, "n_heads": 4,
            "n_kv_heads": 2, "head_dim": 8, "d_ff": 64, "n_layers": 2,
            "max_len": 16, "tie_embeddings": False,
            "param_dtype": "bfloat16", "epochs": 30, "batch_size": 16,
            "lr": 3e-3, "seed": 0}}],
    }
    outcome = run_train(variant=variant, storage=storage)
    assert outcome.status == "COMPLETED"
    server = create_engine_server(
        storage=storage,
        config=ServerConfig(ip="127.0.0.1", port=0, batching=True))
    server.start()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}/queries.json",
            data=json.dumps({"items": ["i3", "i4", "i5"], "num": 3}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as resp:
            scores = json.loads(resp.read())["itemScores"]
        # the item cycle is learnable: after i3 i4 i5 comes i6
        assert scores and scores[0]["item"] == "i6"
        model = server.service.deployed.models[0]
        assert model.cfg.block == "brumby"
        assert all(a.dtype == jnp.bfloat16
                   for a in jax.tree.leaves(model.device_tree))
    finally:
        server.stop()
