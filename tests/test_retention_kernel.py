"""The fused state pass of power retention (``ops/pallas_retention.py``)
in interpret mode on the CPU: one chunk step against the ``jax.numpy``
step, whole sequences against the ``jax.numpy`` path and the plain
reference's quadratic form, the rule that chooses it, and the counter
that reports it. Its speed is the chip's to say (``chip_smoke.py``
compiles and runs it there)."""

from __future__ import annotations

import dataclasses

import json
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import brumby_jnp as ref
from predictionio_tpu.api.stats import ServingStats
from predictionio_tpu.models import seqrec
from predictionio_tpu.ops import pallas_attention, pallas_retention, retention
from predictionio_tpu.templates import sessionrec
from predictionio_tpu.utils.bimap import BiMap

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
    plain, fused = (
        jax.jit(lambda *a, kernel=kernel: retention._power_retention(
            *a, chunk, 1e-6, F32, kernel))(q, k, v, lg)
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
            assert b"pio_serving_seq_fused_retention_programs_total 0" \
                in resp.read()
    finally:
        server.stop()
        model.set_dispatch_observer(None)


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


def test_mosaic_builds_both_ends_of_the_envelope(one_chip):
    """Interpret mode accepts what Mosaic refuses (a slice off the
    tiling, more VMEM than a kernel may use): compile for a described
    v5e, the session cell's shape and the smallest. Nothing runs; the
    chip run is ``chip_smoke.py``'s. One test for all of it (and for
    the ALS top-k programs' compiled text at the end): the TPU's
    compiler loads in one process at a time, and cases of one test
    cannot land on two workers."""
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
