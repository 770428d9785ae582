"""The fused state pass of power retention (``ops/pallas_retention.py``)
in interpret mode on the CPU: one chunk step against the ``jax.numpy``
step, whole sequences against the ``jax.numpy`` path and the plain
reference's quadratic form, the rule that chooses it, and the counter
that reports it; and the fused way in (``ops/pallas_qk_norm.py``: QK-norm,
RoPE, the rounding and retention's chunk order in one pass) against
``ops/qk_norm.prepare`` and the layout ``retention.chunk_order`` defines.
Their speed is the chip's to say (``chip_smoke.py`` compiles and runs
them there)."""

from __future__ import annotations

import dataclasses

import json
import re
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import brumby_jnp as ref
from predictionio_tpu.api.stats import ServingStats
from predictionio_tpu.models import seqrec
from predictionio_tpu.ops import (
    pallas_attention, pallas_qk_norm, pallas_retention, qk_norm, retention)
from predictionio_tpu.templates import sessionrec
from predictionio_tpu.utils.bimap import BiMap
from tests import retention_cases as cases

D = 128
F32 = jnp.float32


def _fused_order(d):
    """For each feature row of the kernel's state, its row in the order
    of ``retention._phi_blocks``."""
    blocks = {i0: (w, offset) for i0, _, w, offset in retention._phi_blocks(d)}
    rows = []
    for ib, jb in pallas_retention.block_pairs(d).T:
        w, offset = blocks[ib * 16]
        rows += [offset + i * w + (jb - ib) * 16 + j
                 for i in range(16) for j in range(16)]
    return np.array(rows)


def _draw(rng, *shape):
    return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)


def test_the_kernels_feature_order_is_a_permutation_of_phi():
    order = _fused_order(D)
    assert sorted(order) == list(range(retention.phi_width(D)))
    assert pallas_retention._BLOCK == retention._PHI_BLOCK
    pairs = pallas_retention.block_pairs(D)
    assert pairs.shape == (2, 36) and bool(np.all(pairs[0] <= pairs[1]))


@jax.jit
def _jnp_step(q, k, vl, keep, state):
    num = retention._read_state(q, state.astype(jnp.bfloat16), 1.0 / D)
    return num, keep[..., None, None] * state + jnp.einsum(
        "bgfs,bgse->bgfe", retention._phi_keys(k), vl,
        preferred_element_type=F32)


@pytest.mark.parametrize("batch,G,R,chunk", [
    (1, 1, 1, 128), (2, 2, 5, 128), (2, 1, 1, 256), (1, 2, 5, 256)])
def test_state_pass_matches_the_jnp_step(batch, G, R, chunk):
    n = batch * G
    rng = np.random.default_rng(chunk + n + R)
    q, k, vl = (_draw(rng, batch, G, R, chunk, D), _draw(rng, batch, G, chunk, D),
                _draw(rng, batch, G, chunk, D))
    keep = jnp.asarray(rng.uniform(0.5, 1.0, (batch, G)), F32)
    state = jnp.asarray(
        rng.standard_normal((batch, G, retention.phi_width(D), D)), F32)
    want_num, want_state = _jnp_step(q, k, vl, keep, state)
    order = _fused_order(D)
    num, new = pallas_retention.state_pass(
        q.reshape(n, R * chunk, D), k.reshape(n, chunk, D),
        vl.reshape(n, chunk, D), keep.reshape(n),
        state.reshape(n, -1, D)[:, order], interpret=True)
    # the same operands into the same products: float32 sums in another
    # order (|num| is ~10, the state ~100)
    np.testing.assert_allclose(
        num.reshape(want_num.shape), want_num, atol=2e-4)
    np.testing.assert_allclose(
        new, want_state.reshape(n, -1, D)[:, order], atol=2e-4)


def _qkvg(seed, batch, S, H, G, gate_logit=6.9):
    rng = np.random.default_rng(seed)
    lg = jax.nn.log_sigmoid(jnp.asarray(
        gate_logit + rng.standard_normal((batch, S, G)), F32))
    return (_draw(rng, batch, S, H, D).astype(F32),
            _draw(rng, batch, S, G, D).astype(F32),
            _draw(rng, batch, S, G, D).astype(F32), lg)


def _rel(got, want):
    return float(jnp.max(jnp.abs(got - want))
                 / jnp.sqrt(jnp.mean(want * want)))


def _both_paths(q, k, v, lg, chunk):
    def through(*a, kernel):
        y = retention._power_retention(
            *retention._chunk_major(*a, chunk), 1e-6, F32, kernel, q.dtype)
        return retention._token_major(y, *q.shape[1:3])

    plain, fused = (
        jax.jit(lambda *a, kernel=kernel: through(*a, kernel=kernel))(
            q, k, v, lg)
        for kernel in (None, "interpret"))
    return plain, fused


@pytest.mark.parametrize("case", [
    dict(batch=1, S=128, H=1, G=1, chunk=128),        # one chunk: no state yet
    dict(batch=1, S=300, H=4, G=2, chunk=128),        # ends inside the third
    dict(batch=2, S=512, H=1, G=1, chunk=256),
], ids=lambda c: "-".join(f"{k}{v}" for k, v in c.items()))
def test_fused_retention_matches_the_jnp_path_and_the_quadratic_form(case):
    chunk = case.pop("chunk")
    q, k, v, lg = _qkvg(7, **case)
    plain, fused = _both_paths(q, k, v, lg, chunk)
    assert fused.shape == q.shape and fused.dtype == q.dtype
    # only the order of float32 sums differs: a last bit of bfloat16
    # where a weight falls on the other side of a rounding
    assert _rel(fused, plain) < 0.01
    for b in range(case["batch"]):
        want = ref.retention_quadratic(q[b], k[b], v[b], lg[b])
        assert _rel(fused[b], want) < 0.05


def test_the_carried_state_matters_and_is_carried():
    """Gates near one over three chunks: the last chunk's answers come
    mostly from the chunks before it."""
    S, chunk = 384, 128
    q, k, v, lg = _qkvg(9, 1, S, 2, 1, gate_logit=12.0)
    plain, fused = _both_paths(q, k, v, lg, chunk)
    want = ref.retention_quadratic(q[0], k[0], v[0], lg[0])
    assert _rel(fused, plain) < 0.01 and _rel(fused[0], want) < 0.05
    alone = ref.retention_quadratic(
        q[0, -chunk:], k[0, -chunk:], v[0, -chunk:], lg[0, -chunk:])
    assert _rel(fused[0, -chunk:], alone) > 0.5


# -- the rule ----------------------------------------------------------------

def test_only_a_compiled_backend_at_an_eligible_shape_fuses(monkeypatch):
    ask = retention.fuses_state_pass
    assert pallas_attention._mode() == "interpret"      # the CPU
    assert not ask(D, 5, 256, inference=True)
    monkeypatch.setattr(pallas_attention, "_mode", lambda: "compiled")
    assert ask(D, 5, 256, inference=True)               # the cell's shape
    assert ask(D, 1, 128, inference=True)               # the smallest
    assert not ask(D, 5, 256, inference=False)          # a gradient may follow
    assert not ask(16, 2, 64, inference=True)
    assert not ask(32, 2, 128, inference=True)
    assert not ask(D, 5, 64, inference=True)            # a chunk under a tile
    assert not ask(D, 5, 256, inference=True, state_dtype=jnp.bfloat16)
    assert not ask(D, 64, 256, inference=True)          # blocks beyond VMEM
    assert pallas_retention.vmem_bytes(D, 5 * 256, 256) \
        <= pallas_retention._VMEM_BUDGET


def test_on_the_cpu_inference_runs_todays_path():
    q, k, v, lg = _qkvg(3, 1, 128, 2, 1)
    jaxpr = str(jax.make_jaxpr(lambda *a: retention.power_retention(
        *a, inference=True))(q, k, v, lg))
    assert "pallas_call" not in jaxpr
    np.testing.assert_array_equal(
        retention.power_retention(q, k, v, lg, inference=True),
        retention.power_retention(q, k, v, lg))


def test_a_kernel_that_cannot_be_built_raises(monkeypatch):
    """Inside the envelope there is no way back to XLA: here the rule is
    told the backend compiles, and the CPU cannot."""
    monkeypatch.setattr(pallas_attention, "_mode", lambda: "compiled")
    q, k, v, lg = _qkvg(3, 1, 128, 2, 1)
    with pytest.raises(Exception, match="(?i)interpret|pallas|mosaic|tpu"):
        jax.block_until_ready(
            retention.power_retention(q, k, v, lg, inference=True))
    # the same call with a gradient in sight takes the jax.numpy step
    jax.block_until_ready(retention.power_retention(q, k, v, lg))


# -- the model and the counter -----------------------------------------------

ITEMS, S = 60, 128
PARAMS = dict(backbone="brumby", d_model=128, n_heads=2, n_kv_heads=1,
              head_dim=D, d_ff=64, n_layers=1, max_len=S,
              tie_embeddings=False, param_dtype="bfloat16", use_mesh=False)


@pytest.fixture(scope="module")
def engine_model():
    params = sessionrec.AlgorithmParams(**PARAMS)
    cfg = params.seqrec_config(vocab=ITEMS + 1)
    weights = jax.tree.map(np.array, seqrec.init_params(
        jax.random.PRNGKey(5), cfg))
    rng = np.random.default_rng(6)
    model = sessionrec.SeqRecEngineModel(
        params=weights, cfg=cfg,
        item_index=BiMap({f"i{k}": k + 1 for k in range(ITEMS)}),
        histories={f"u{u}": rng.integers(1, ITEMS + 1, size=n).astype(np.int32)
                   for u, n in enumerate([S, 40])})
    return sessionrec.SeqRecAlgorithm(params), model


def test_gradients_through_the_block_stack_take_the_jnp_step(
        engine_model, monkeypatch):
    """Training enters with ``inference=False``: what the rule says of
    the backend changes nothing, and nothing tries to build a kernel."""
    _, model = engine_model
    history = jnp.asarray(model.histories["u0"][None, :])

    def loss(params):
        return seqrec.next_item_loss(
            params, history[:, :-1], history[:, 1:], model.cfg)

    before = jax.jit(jax.grad(loss))(model.params)
    monkeypatch.setattr(pallas_attention, "_mode", lambda: "compiled")
    assert seqrec.fuses_retention(model.cfg, S)
    # traced anew: the rule is asked again, and answers for inference only
    after = jax.jit(jax.grad(lambda params: loss(params)))(model.params)
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        assert bool(jnp.all(jnp.isfinite(a)))
        np.testing.assert_array_equal(a, b)


def test_the_dispatch_reports_fused_programs_when_the_rule_says_so(
        engine_model, monkeypatch):
    algo, model = engine_model
    assert not seqrec.fuses_retention(seqrec.SeqRecConfig(vocab=11), 16384)
    seen = []
    model.set_dispatch_observer(lambda *a: seen.append(a))
    queries = [(0, sessionrec.Query(user="u0", num=3)),
               (1, sessionrec.Query(user="u1", num=3))]
    try:
        assert not seqrec.fuses_retention(model.cfg, S)          # the CPU
        algo.batch_predict(model, queries)
        # the answer the dispatch would get on a chip; the programs
        # themselves are compiled already and run as they were
        monkeypatch.setattr(seqrec, "fuses_retention", lambda cfg, s: True)
        algo.batch_predict(model, queries)
    finally:
        model.set_dispatch_observer(None)
    # one record a dispatch (sessionrec.SeqDispatch), always whole: the
    # count is a named field, 0 where no program fused
    assert [dataclasses.astuple(r)[:5] for (r,) in seen] == \
        [(1, S + 40, 2 * S, 0, 0), (1, S + 40, 2 * S, 0, 1)]
    stats = ServingStats()
    for report in seen:
        stats.record_seq_dispatch(*report)
    assert stats.count("seq_programs") == 2
    assert stats.count("seq_fused_retention_programs") == 1


def test_stats_and_metrics_show_the_counter_at_zero_on_the_cpu(engine_model):
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
        ServerConfig(ip="127.0.0.1", port=0, batching=True))
    server.start()
    try:
        base = f"http://127.0.0.1:{server.port}"
        req = urllib.request.Request(
            f"{base}/queries.json",
            data=json.dumps({"user": "u0", "num": 3}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as resp:
            assert len(json.loads(resp.read())["itemScores"]) == 3
        with urllib.request.urlopen(f"{base}/stats.json") as resp:
            serving = json.loads(resp.read())["serving"]
        assert serving["seqPrograms"] == 1
        assert serving["seqFusedRetentionPrograms"] == 0
        # no ALS top-k runs behind the session engine (PR 30's counter)
        assert serving["topkTwoStageDispatches"] == 0
        with urllib.request.urlopen(f"{base}/metrics") as resp:
            metrics = resp.read()
        assert b"pio_serving_seq_fused_retention_programs_total 0" in metrics
        assert b"pio_serving_seq_fused_qk_norm_programs_total 0" in metrics
        assert serving["seqFusedQkNormPrograms"] == 0
    finally:
        server.stop()
        model.set_dispatch_observer(None)


# -- the way in: the kernel against the jax.numpy form ------------------------

def _projection(seed, B, S, heads):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((B, S, heads * D)) * 1.5, jnp.bfloat16)
    weight = jnp.asarray(2.0 + 0.1 * rng.standard_normal(D), jnp.bfloat16)
    return x, weight


def _within_one_ulp(got, want):
    """bfloat16 results of the same equations where only a 128-lane
    sum's order differs: equal nearly everywhere, a last bit apart
    elsewhere (8 bits of significand: 2**-7 of the value)."""
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert got.shape == want.shape
    apart = np.abs(got - want)
    assert float(np.mean(apart == 0)) > 0.97
    assert np.all(apart <= 2.0 ** -7 * np.maximum(np.abs(want), 2.0 ** -20))


WAY_IN = [
    # the MiniCPM-SALA lightning layer's
    dict(B=1, S=64, heads=2, chunk=32, theta=1e4),
    dict(B=2, S=96, heads=3, chunk=16, theta=1e4),
    # Brumby's: its code rounds the norm to bfloat16 before the rotation
    # (the CPU does, the TPU does not: ops/qk_norm.prepare)
    dict(B=2, S=64, heads=5, chunk=32, theta=1e6, norm_dtype=jnp.bfloat16),
    dict(B=1, S=256, heads=1, chunk=256, theta=1e6, norm_dtype=jnp.bfloat16),
    # the sparse layers': no positions, 1 / sqrt(d) folded in, token-major
    dict(B=2, S=48, heads=2, chunk=None, theta=None, scale=128 ** -0.5),
    dict(B=1, S=32, heads=4, chunk=None, theta=1e4),
]


@pytest.mark.parametrize(
    "case", WAY_IN, ids=lambda c: "-".join(f"{k}{v}" for k, v in c.items()
                                           if k != "norm_dtype"))
def test_the_fused_way_in_matches_the_jnp_form_in_its_layout(case):
    case = dict(case)
    B, S, heads, chunk, theta = (case.pop(k) for k in (
        "B", "S", "heads", "chunk", "theta"))
    x, weight = _projection(S + heads, B, S, heads)
    rope = None if theta is None else qk_norm.rope_tables(S, D, theta)
    norm_dtype = case.pop("norm_dtype", None)
    how = dict(heads=heads, eps=1e-6, rope=rope, **case)

    def laid_out(y):
        assert y.shape == (B, S, heads, D) and y.dtype == x.dtype
        return retention.chunk_order(y, chunk) if chunk else y

    # the kernel computes what the TPU makes of the jnp form: float32
    # from the norm to the one rounding, whatever norm_dtype writes
    got = qk_norm.fused(x, weight, chunk=chunk, interpret=True, **how)
    assert got.dtype == x.dtype
    if chunk:
        assert got.shape == (S // chunk, B, heads, chunk, D)
    _within_one_ulp(got, laid_out(qk_norm.prepare(x, weight, **how)))
    # and the jnp form is the parent's formulation bit for bit, the
    # rounding after Brumby's norm (performed here, on the CPU) included
    np.testing.assert_array_equal(
        np.asarray(laid_out(qk_norm.prepare(
            x, weight, norm_dtype=norm_dtype, **how)), np.float32),
        np.asarray(cases.plain_way_in(
            x, weight, heads=heads, eps=1e-6, theta=theta, chunk=chunk,
            norm_dtype=norm_dtype, **case), np.float32))


def test_rope_tables_hold_both_halves_and_the_sign():
    cos, sin = qk_norm.rope_tables(40, 16, 1e4)
    assert cos.shape == sin.shape == (40, 16) and cos.dtype == F32
    np.testing.assert_array_equal(cos[:, :8], cos[:, 8:])
    np.testing.assert_array_equal(sin[:, :8], -sin[:, 8:])
    inv = 1e4 ** (-np.arange(0, 16, 2, dtype=np.float32) / 16)
    np.testing.assert_allclose(
        sin[:, 8:], np.sin(np.arange(40, dtype=np.float32)[:, None] * inv),
        atol=1e-6)


def test_the_way_in_fuses_by_backend_gradient_and_shape(monkeypatch):
    ask = qk_norm.fuses
    assert not ask(D, 32 * D, 256, inference=True)           # the CPU
    monkeypatch.setattr(pallas_attention, "_mode", lambda: "compiled")
    assert ask(D, 32 * D, 256, inference=True)      # MiniCPM-SALA's q and k
    assert ask(D, 40 * D, 256, inference=True)      # Brumby's q
    assert ask(D, 8 * D, 256, inference=True)       # and k
    assert ask(D, 2 * D, 32768, inference=True)     # the sparse layers' k
    assert not ask(D, 32 * D, 256, inference=False)  # a gradient may follow
    assert not ask(16, 4 * 16, 32, inference=True)  # the tiny presets
    assert not ask(64, 8 * 64, 256, inference=True)
    assert not ask(D, 32 * D, 8, inference=True)    # under a bfloat16 tile
    assert not ask(D, 32 * D, 200, inference=True)  # no step divides it
    # a step's blocks, twice each, stay inside the budget: rows halve
    assert pallas_qk_norm.rows_per_step(256, 32 * D) == 256
    assert pallas_qk_norm.rows_per_step(256, 40 * D) == 128
    assert pallas_qk_norm.rows_per_step(32768, 2 * D) == 256
    assert pallas_qk_norm.rows_per_step(48, 2 * D) == 16
    with pytest.raises(ValueError, match="128-wide heads"):
        pallas_qk_norm.qk_norm_rope(
            jnp.zeros((1, 32, 64), jnp.bfloat16), jnp.ones((16,)), None, None,
            heads=4, eps=1e-6, interpret=True)


def test_retention_takes_the_fused_way_in_when_the_rule_says_so(monkeypatch):
    """``_chunk_major`` with a way in: on the CPU the jnp form moved by
    ``chunk_order``; where the rule holds, the kernel's own chunk order,
    the same operands within an ulp (here the rule is forced and the
    kernel runs in interpret mode)."""
    B, S, H, G, C = 2, 80, 4, 2, 32                      # S is 2.5 chunks
    rng = np.random.default_rng(11)
    q, wq = _projection(1, B, S, H)
    k, wk = _projection(2, B, S, G)
    v = _draw(rng, B, S, G, D)
    lg = jnp.asarray(-np.abs(rng.standard_normal((B, S, G))) * 0.05, F32)
    way_in = retention.WayIn(wq, wk, 1e-6, qk_norm.rope_tables(S, D, 1e4))
    args = (q.reshape(B, S, H, D), k.reshape(B, S, G, D), v, lg, C, way_in)
    plain = retention._chunk_major(*args, inference=True)
    assert "pallas_call" not in str(jax.make_jaxpr(
        lambda *a: retention._chunk_major(*a, C, way_in, True))(*args[:4]))
    monkeypatch.setattr(qk_norm, "fuses", lambda *a, **kw: True)
    monkeypatch.setattr(
        qk_norm, "fused", lambda *a, fused=qk_norm.fused, **kw: fused(
            *a, interpret=True, **kw))
    fused = retention._chunk_major(*args, inference=True)
    for got, want in zip(fused, plain):
        assert got.shape == want.shape and got.dtype == want.dtype
    assert fused[0].shape == (3, B, G, H // G, C, D)
    _within_one_ulp(fused[0], plain[0])
    _within_one_ulp(fused[1], plain[1])
    np.testing.assert_array_equal(np.asarray(fused[2], np.float32),
                                  np.asarray(plain[2], np.float32))
    # the padding after the last position is zeros in both
    assert not np.any(np.asarray(fused[0], np.float32)[2, :, :, :, S % C:])


def test_the_dispatch_reports_the_fused_way_in_beside_the_state_pass(
        engine_model, monkeypatch):
    algo, model = engine_model
    assert not seqrec.fuses_qk_norm(seqrec.SeqRecConfig(vocab=11), 16384)
    assert not seqrec.fuses_qk_norm(model.cfg, S)                # the CPU
    full = sessionrec.AlgorithmParams(
        backbone="brumby", d_model=5120, n_heads=40, n_kv_heads=8,
        head_dim=128, d_ff=17408, n_layers=4,
        max_len=16384).seqrec_config(vocab=151936)
    assert seqrec.BLOCKS["brumby"].kernels(full, 16384) == ()
    monkeypatch.setattr(pallas_attention, "_mode", lambda: "compiled")
    assert seqrec.BLOCKS["brumby"].kernels(full, 16384) == (
        "retention_state_pass", "qk_norm_rope")
    assert seqrec.fuses_qk_norm(full, 16384)
    assert seqrec.fuses_qk_norm(model.cfg, S)         # heads of 128 here
    assert not seqrec.fuses_qk_norm(
        dataclasses.replace(full, head_dim=16), 16384)
    monkeypatch.undo()
    seen = []
    model.set_dispatch_observer(seen.append)
    try:
        monkeypatch.setattr(seqrec, "fuses_qk_norm", lambda cfg, s: True)
        algo.batch_predict(model, [(0, sessionrec.Query(user="u0", num=3))])
    finally:
        model.set_dispatch_observer(None)
    assert [(r.programs, r.fused_retention_programs, r.fused_qk_norm_programs)
            for r in seen] == [(1, 0, 1)]
    stats = ServingStats()
    stats.record_seq_dispatch(seen[0])
    assert stats.count("seq_fused_qk_norm_programs") == 1
    assert stats.count("seq_fused_retention_programs") == 0


# -- the chip's compiler, without the chip -----------------------------------

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_mosaic_builds_both_ends_of_the_envelope(one_chip, monkeypatch):
    """Interpret mode accepts what Mosaic refuses (a slice off the
    tiling, more VMEM than a kernel may use): compile for a described
    v5e, the session cell's shape and the smallest. Nothing runs; the
    chip run is ``chip_smoke.py``'s. One test for all of it (and for
    the ALS top-k programs' and a MiniCPM-SALA period's compiled text at
    the end): the TPU's compiler loads in one process at a time, and
    cases of one test cannot land on two workers."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    bf16 = jnp.bfloat16
    for n, ratio, chunk in ((8, 5, 256), (1, 1, 128)):
        assert pallas_retention.in_envelope(D, ratio * chunk, chunk)
        lowered = jax.jit(
            lambda *a: pallas_retention.state_pass(*a, interpret=False),
            donate_argnums=(4,)
        ).lower(sds((n, ratio * chunk, D), bf16), sds((n, chunk, D), bf16),
                sds((n, chunk, D), bf16), sds((n,), F32),
                sds((n, retention.phi_width(D), D), F32))
        assert "tpu_custom_call" in lowered.compile().as_text()
    # the sparse layer's two kernels (ops/sparse_attention.py) at the
    # MiniCPM-SALA cell's shape, in this test for the reason above
    from predictionio_tpu.ops import sparse_attention as sa

    sz, S, G, R = sa.SparseSizes(), 32768, 2, 16
    q, k = sds((1, S, G * R * D), bf16), sds((1, S, G * D), bf16)
    kept = sds((1, G, S, sa.n_blocks(S, sz)), jnp.bool_)

    def stage_2(q, k, v, kept):
        visits = sa.visit_map(kept, S, sa.TILE_Q, sa.TILE_K, sz.block_size)
        return sa.attention(q, k, v, kept, visits, groups=G,
                            block=sz.block_size)

    for fn, args in ((lambda q, k: sa.selection_scores(
            q, k, sz=sz, groups=G), (q, k)), (stage_2, (q, k, k, kept))):
        assert "tpu_custom_call" in jax.jit(fn).lower(*args).compile() \
            .as_text()
    # the ALS top-k programs at the Books cells' shape (ops/topk.py), in
    # this test for the same reason: from the model's bfloat16 serving
    # copy no program holds a float32 array of the table's shape (a
    # hoisted convert would move 2.25 GB a dispatch where the scan
    # moves 1.13; inside the B = 1 fusion the widening is not an array)
    from predictionio_tpu.ops import topk

    items, rank = 4_400_000, 128
    for b in (1, 8, 16):
        compiled = jax.jit(
            topk.recommend_topk_rows.__wrapped__, static_argnames="k"
        ).lower(sds((1024, rank), F32), sds((b,), jnp.int32),
                sds((items, rank), bf16), sds((b, 8), jnp.int32),
                sds((b, 8), F32), sds((items,), F32), k=10).compile()
        text = compiled.as_text()
        entry = text[text.index("ENTRY"):]
        assert f"bf16[{items},{rank}]" in entry
        assert f"f32[{items},{rank}]" not in entry
        # scores and selection only: 8 or 16 rows of float32 scores
        assert compiled.memory_analysis().temp_size_in_bytes \
            < 1.1 * max(b, 8) * items * 4

    # the fused way in (ops/pallas_qk_norm.py) at the widths the session
    # cells run: MiniCPM-SALA's lightning q and k, Brumby's q (40 heads:
    # a step of 128 rows) and k, the sparse layers' q and k token-major
    # without positions
    cos = sin = sds((32768, D), F32)
    for heads, how in ((32, dict(chunk=256)),
                       (40, dict(chunk=256)),
                       (8, dict(chunk=256)),
                       (32, dict(rope=False, scale=D ** -0.5)),
                       (2, dict(rope=False))):
        rope = (cos, sin) if how.pop("rope", True) else (None, None)
        assert pallas_qk_norm.in_envelope(D, heads * D, how.get("chunk", S))
        assert "tpu_custom_call" in jax.jit(
            lambda x, w, c, s, heads=heads, how=how:
            pallas_qk_norm.qk_norm_rope(x, w, c, s, heads=heads, eps=1e-6,
                                        interpret=False, **how)
        ).lower(sds((1, S, heads * D), bf16), sds((D,), bf16), *rope) \
            .compile().as_text()
    # one period of MiniCPM-SALA at the cell's widths (a sparse and a
    # lightning layer over 32,768 events), as a serving program: between
    # a mixer's projections and its output projection no float32 array
    # of the (S, heads, d) shape exists, the way in is the kernel, and
    # the scan over chunks is still a top-level while (two benchmark
    # readers time that op)
    monkeypatch.setattr(pallas_attention, "_mode", lambda: "compiled")
    cfg = sessionrec.AlgorithmParams(
        backbone="minicpm_sala", d_model=4096, n_heads=32, n_kv_heads=2,
        head_dim=128, d_ff=16384, n_layers=2, max_len=S,
        tie_embeddings=False, param_dtype="bfloat16",
        sala={"mixer_types": ["minicpm4", "lightning-attn"]}
    ).seqrec_config(vocab=4096)
    weights = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda key: seqrec.init_params(key, cfg, bf16),
                       jax.random.PRNGKey(0)))
    text = seqrec.predict_topk_batch.lower(
        weights, sds((1, S), jnp.int32), 10, cfg,
        sds((1, cfg.vocab), F32)).compile().as_text()
    entry = text[text.index("ENTRY"):]
    assert entry.count("qk_norm_rope") >= 4      # sparse q, k; lightning q, k
    assert len(re.findall(r"^ *%while[.\d]* = \(.*\) while\(", entry,
                          re.M)) == 1
    for shape in (f"f32[1,{S},32,{D}]", f"f32[{S},32,{D}]",
                  f"f32[1,{S},32,64]", f"f32[1,{S // 256},256,32,1,{D}]"):
        assert shape not in entry, shape
