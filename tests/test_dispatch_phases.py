"""The way back to the host of a batched dispatch
(``serving/dispatch_phases``): the copy of every output is started when
the program is launched (``dispatch.copy_start``, inside
``dispatch.enqueue``) and only collected by ``await_and_fetch``; both
templates start their copies once per device program, and the traced
phases read as they did."""

from __future__ import annotations

import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.readers import read_metric
from predictionio_tpu.models import als as als_mod
from predictionio_tpu.models import seqrec
from predictionio_tpu.obs.trace import start_trace, use_trace
from predictionio_tpu.serving import dispatch_phases
from predictionio_tpu.serving.dispatch_phases import (
    await_and_fetch,
    start_copies,
)
from predictionio_tpu.templates import recommendation as rec
from predictionio_tpu.templates import sessionrec
from predictionio_tpu.utils.bimap import BiMap, EntityIdIxMap
from tests.rec_engine import DISPATCH_PHASES

pytestmark = pytest.mark.obs


class RecordingArray:
    """Stands in for a device array: says when its copy was started,
    when it was waited for and when it was read."""

    def __init__(self, name: str, value: np.ndarray, log: list):
        self.name, self.value, self.log = name, value, log

    def copy_to_host_async(self):
        self.log.append(("copy_start", self.name))

    def block_until_ready(self):
        self.log.append(("ready", self.name))
        return self

    def __array__(self, dtype=None, copy=None):
        self.log.append(("asarray", self.name))
        return self.value


def _values(n: int) -> list[np.ndarray]:
    rng = np.random.default_rng(n)
    return [rng.standard_normal((3, 5)).astype(np.float32) if i % 2 == 0
            else rng.integers(0, 99, (3, 5)).astype(np.int32)
            for i in range(n)]


@pytest.mark.parametrize("traced", [False, True], ids=["untraced", "traced"])
@pytest.mark.parametrize("outputs", [1, 2, 3])
def test_every_copy_starts_before_any_wait_or_read(outputs, traced):
    log: list = []
    values = _values(outputs)
    arrays = tuple(RecordingArray(f"o{i}", v, log)
                   for i, v in enumerate(values))
    trace = start_trace("dispatch") if traced else None
    with use_trace(trace):
        launched = start_copies(arrays)
        assert launched is arrays
        assert log == [("copy_start", f"o{i}") for i in range(outputs)]
        got = await_and_fetch(launched)
    later = log[outputs:]
    assert "copy_start" not in {kind for kind, _ in later}
    reads = [name for kind, name in later if kind == "asarray"]
    assert reads == [f"o{i}" for i in range(outputs)]
    # traced, the wait for the device comes before the first read
    waits = [name for kind, name in later if kind == "ready"]
    assert waits == ([f"o{i}" for i in range(outputs)] if traced else [])
    if traced:
        assert later[:outputs] == [("ready", f"o{i}") for i in range(outputs)]
    assert len(got) == outputs
    for g, v in zip(got, values):
        assert g.dtype == v.dtype
        np.testing.assert_array_equal(g, v)
    names = [s[0] for s in trace.spans()] if traced else []
    assert names == (["dispatch.copy_start", "dispatch.device_wait",
                      "dispatch.fetch"] if traced else [])


@pytest.mark.parametrize("make", [
    lambda log: (np.arange(6.0).reshape(2, 3), np.arange(6).reshape(2, 3)),
    lambda log: ([1.5, 2.5], [3, 4]),
    lambda log: (),
    lambda log: (np.float32(2.0), RecordingArray("dev", np.arange(4), log),
                 np.arange(3)),
], ids=["numpy", "lists", "nothing", "numpy_beside_device"])
def test_an_output_that_is_no_device_array_passes_through(make):
    log: list = []
    arrays = make(log)
    assert start_copies(arrays) is arrays
    # only what has a copy to start was asked to start one
    assert log == [("copy_start", "dev")] * sum(
        isinstance(a, RecordingArray) for a in arrays)
    got = await_and_fetch(arrays)
    assert len(got) == len(arrays)
    for g, a in zip(got, arrays):
        want = a.value if isinstance(a, RecordingArray) else np.asarray(a)
        np.testing.assert_array_equal(g, want)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32, jnp.bfloat16])
@pytest.mark.parametrize("order", ["started_at_launch", "not_started"])
def test_device_arrays_come_back_bit_for_bit(dtype, order):
    """A real program's outputs: the bytes fetched are the same whether
    or not their copy was started first, and a copy started on a result
    that is not ready yet does not block or fail."""
    @jax.jit
    def program(x):
        y = jnp.cumsum(x.astype(jnp.float32) * 1.25, axis=1)
        return y.astype(dtype), jnp.argsort(y, axis=1).astype(jnp.int32)

    x = np.random.default_rng(3).standard_normal((8, 40)).astype(np.float32)
    want = tuple(np.asarray(a) for a in program(x))
    launched = program(x)
    if order == "started_at_launch":
        assert start_copies(launched) is launched
    got = await_and_fetch(launched)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.tobytes() == w.tobytes()


# -- the recommendation template ---------------------------------------------

RANK, USERS, ITEMS = 16, 32, 2048


def _als_model(retrieval: str):
    rng = np.random.default_rng(31)
    centres = rng.standard_normal((64, RANK)).astype(np.float32)
    items = (centres[rng.integers(0, 64, ITEMS)]
             + 0.1 * rng.standard_normal((ITEMS, RANK))).astype(np.float32)
    users = rng.standard_normal((USERS, RANK)).astype(np.float32)
    model = als_mod.ALSModel(
        rank=RANK, user_factors=jnp.asarray(users),
        item_factors=jnp.asarray(items),
        user_ids=EntityIdIxMap.from_ids([f"u{i}" for i in range(USERS)]),
        item_ids=EntityIdIxMap.from_ids([f"i{i}" for i in range(ITEMS)]),
        seen_by_user={0: np.asarray([3, 4, 5], dtype=np.int32)})
    if retrieval == "ann":
        model.configure_retrieval("ann")
        assert model.ann_enabled
    return model


def _sharded_als_model():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual mesh")
    mesh = Mesh(np.asarray(jax.devices()).reshape((1, 8)), ("data", "model"))
    rows = NamedSharding(mesh, P("model", None))
    rng = np.random.default_rng(32)
    model = als_mod.ALSModel(
        rank=8,
        user_factors=jnp.asarray(
            rng.standard_normal((40, 8)).astype(np.float32)),
        item_factors=jax.device_put(
            rng.standard_normal((64, 8)).astype(np.float32), rows),
        user_ids=EntityIdIxMap.from_ids([f"u{i}" for i in range(40)]),
        item_ids=EntityIdIxMap.from_ids([f"i{i}" for i in range(64)]),
        seen_by_user={0: np.asarray([3, 4, 5], dtype=np.int32)})
    assert model.factor_shard_ways == 8
    return model


def _rec_case(branch: str):
    model = _sharded_als_model() if branch == "sharded" else _als_model(branch)
    algo = rec.ALSAlgorithm(rec.ALSAlgorithmParams(
        rank=model.rank, exclude_seen=True, use_mesh=False))
    queries = [(0, rec.Query(user="u0", num=5)),
               (1, rec.Query(user="nobody", num=5)),
               (2, rec.Query(user="u7", num=3)),
               (3, rec.Query(user="u9", num=10))]
    return algo, model, queries


class _Recorder:
    """Wraps ``start_copies`` where a template looks it up, and the
    launches before it, so the order of the two is on one list."""

    def __init__(self, monkeypatch, module, launches):
        self.log: list = []
        real_start = dispatch_phases.start_copies

        def start(arrays):
            self.log.append(("copy_start", tuple(arrays)))
            return real_start(arrays)

        monkeypatch.setattr(module, "start_copies", start)
        for owner, name in launches:
            monkeypatch.setattr(owner, name, self._launch(getattr(owner, name)))

    def _launch(self, fn):
        def launch(*args, **kwargs):
            out = fn(*args, **kwargs)
            self.log.append(("launch", tuple(out)))
            return out
        return launch

    def kinds(self):
        return [kind for kind, _ in self.log]


_ALS_LAUNCHES = [(als_mod.topk_ops, "recommend_topk_fused_rows"),
                 (als_mod.ann_ops, "ann_topk"),
                 (als_mod.topk_ops, "recommend_topk_sharded")]


@pytest.mark.parametrize("branch", ["brute", "ann", "sharded"])
def test_recommendation_starts_its_copies_once_a_dispatch(
        branch, monkeypatch):
    algo, model, queries = _rec_case(branch)
    want = dict(algo.batch_predict(model, queries))
    seen = _Recorder(monkeypatch, als_mod, _ALS_LAUNCHES)
    got = dict(algo.batch_predict(model, queries))
    # one launch, then the copies of exactly what it returned
    assert seen.kinds() == ["launch", "copy_start"]
    (_, launched), (_, started) = seen.log
    assert len(started) == 2
    assert all(a is b for a, b in zip(launched, started))
    assert all(isinstance(a, jax.Array) for a in started)
    assert got == want and got[1].item_scores == ()
    assert len(got[0].item_scores) == 5
    assert not {s.item for s in got[0].item_scores} & {"i3", "i4", "i5"}


def test_recommendation_with_nothing_to_batch_starts_no_copy(monkeypatch):
    algo, model, _ = _rec_case("brute")
    seen = _Recorder(monkeypatch, als_mod, _ALS_LAUNCHES)
    got = algo.batch_predict(model, [(0, rec.Query(user="nobody", num=5))])
    assert seen.log == [] and got[0][1].item_scores == ()


# -- the session template ----------------------------------------------------

S, VOCAB_ITEMS = 16, 50


@pytest.fixture(scope="module")
def session_case():
    params = sessionrec.AlgorithmParams(
        d_model=16, n_heads=2, n_layers=1, max_len=S, use_mesh=False)
    cfg = params.seqrec_config(vocab=VOCAB_ITEMS + 1)
    weights = jax.tree.map(np.array, seqrec.init_params(
        jax.random.PRNGKey(5), cfg))
    rng = np.random.default_rng(6)
    histories = {f"u{u}": rng.integers(1, VOCAB_ITEMS + 1, n).astype(np.int32)
                 for u, n in enumerate([S, 9, 4, S])}
    model = sessionrec.SeqRecEngineModel(
        params=weights, cfg=cfg,
        item_index=BiMap({f"i{k}": k + 1 for k in range(VOCAB_ITEMS)}),
        histories=histories)
    queries = [(i, sessionrec.Query(user=f"u{i}", num=4)) for i in range(4)]
    queries.append((4, sessionrec.Query(user="nobody", num=4)))
    return sessionrec.SeqRecAlgorithm(params), model, queries


@pytest.mark.parametrize("histories_a_program,programs", [(4, 1), (2, 2),
                                                          (1, 4)])
def test_sessionrec_starts_its_copies_once_a_program(
        session_case, monkeypatch, histories_a_program, programs):
    algo, model, queries = session_case
    model.budget = 4 * S
    want = dict(algo.batch_predict(model, queries))
    model.budget = histories_a_program * S     # the token budget splits
    seen = _Recorder(monkeypatch, sessionrec,
                     [(sessionrec.seqrec, "predict_topk_batch")])
    try:
        got = dict(algo.batch_predict(model, queries))
    finally:
        model.budget = 0
    assert seen.kinds() == ["launch", "copy_start"] * programs
    for (_, launched), (_, started) in zip(seen.log[::2], seen.log[1::2]):
        assert len(started) == 2      # scores and ids; no routed layer here
        assert all(a is b for a, b in zip(launched, started))
    assert got[4].item_scores == ()
    for i in range(4):
        assert [s.item for s in got[i].item_scores] == \
            [s.item for s in want[i].item_scores]
        np.testing.assert_allclose([s.score for s in got[i].item_scores],
                                   [s.score for s in want[i].item_scores],
                                   atol=1e-5)


# -- traced: the span tree and the metrics that read it ----------------------

def _traced(run) -> list[tuple[str, float, float]]:
    """(name, start, end) of what ``run`` records on a per-dispatch
    trace, bound the way the batcher binds one."""
    trace = start_trace("batcher.dispatch")
    with use_trace(trace):
        run()
    return [(name, start, start + dur)
            for name, _, _, start, dur in trace.spans()]


def _check_span_tree(spans, programs: int):
    by_name: dict[str, list] = {}
    for name, start, end in spans:
        by_name.setdefault(name, []).append((start, end))
    assert set(DISPATCH_PHASES) <= set(by_name), sorted(by_name)
    assert len(by_name["dispatch.copy_start"]) == programs
    assert len(by_name["dispatch.enqueue"]) == programs
    assert len(by_name["dispatch.fetch"]) == programs
    # each copy_start lies inside the enqueue of its own program, and
    # ends before that program is waited for or fetched
    for (cs, ce), (es, ee), (fs, _), (ws, _) in zip(
            by_name["dispatch.copy_start"], by_name["dispatch.enqueue"],
            by_name["dispatch.fetch"], by_name["dispatch.device_wait"]):
        assert es <= cs <= ce <= ee <= ws <= fs
    return by_name


def _self_ms(spans, total_s: float) -> tuple[float, float]:
    """(``dispatch_self_ms`` as its reader reads it, the same by hand
    from the six phases alone)."""
    sums = {"batcher.device_dispatch": total_s}
    for name, start, end in spans:
        sums[name] = sums.get(name, 0.0) + (end - start)
    ev = {"requests": [sums], "spans": {k: [v] for k, v in sums.items()}}
    by_hand = (total_s - sum(sums[p] for p in DISPATCH_PHASES)) * 1e3
    return read_metric("dispatch_self_ms", ev), by_hand


@pytest.mark.parametrize("branch", ["brute", "ann"])
def test_traced_recommendation_dispatch_has_copy_start_under_enqueue(branch):
    algo, model, queries = _rec_case(branch)
    algo.batch_predict(model, queries)          # compiled before the trace
    spans = _traced(lambda: algo.batch_predict(model, queries))
    _check_span_tree(spans, programs=1)
    assert [n for n, _, _ in spans if n in DISPATCH_PHASES] == \
        list(DISPATCH_PHASES)
    total = max(e for _, _, e in spans) - min(s for _, s, _ in spans) + 1e-3
    got, by_hand = _self_ms(spans, total)
    # copy_start is inside enqueue, not a seventh child: taken off once
    assert got == pytest.approx(by_hand) and got >= 1.0


def test_traced_sessionrec_dispatch_has_one_copy_start_a_program(
        session_case):
    algo, model, queries = session_case
    model.budget = 2 * S
    try:
        algo.batch_predict(model, queries)
        spans = _traced(lambda: algo.batch_predict(model, queries))
    finally:
        model.budget = 0
    _check_span_tree(spans, programs=2)
    total = max(e for _, _, e in spans) - min(s for _, s, _ in spans) + 1e-3
    got, by_hand = _self_ms(spans, total)
    assert got == pytest.approx(by_hand) and got >= 1.0


def test_untraced_dispatch_never_waits_for_the_device(monkeypatch):
    """With no trace bound there is no ``block_until_ready`` call: the
    first ``np.asarray`` is the one sync, and the answers are the same."""
    algo, model, queries = _rec_case("brute")
    want = dict(algo.batch_predict(model, queries))

    def no_wait(_):
        raise AssertionError("block_until_ready on an untraced dispatch")

    monkeypatch.setattr(jax, "block_until_ready", no_wait)
    assert dict(algo.batch_predict(model, queries)) == want
    with pytest.raises(AssertionError, match="untraced"):
        _traced(lambda: algo.batch_predict(model, queries))


def test_a_traced_query_through_the_server_carries_copy_start():
    """The whole path: the batcher copies the per-dispatch trace onto the
    request's, ``dispatch.copy_start`` with it, once, inside
    ``dispatch.enqueue``; the six phases and ``dispatch_self_ms`` are
    what ``tests/benchmarks/test_dispatch_spans.py`` expects."""
    from predictionio_tpu.utils.testing import memory_storage
    from tests.rec_engine import post_query, start_rec_server, train_rec

    storage = memory_storage()
    with pytest.MonkeyPatch.context() as mp, \
            tempfile.TemporaryDirectory() as model_dir:
        train_rec(storage, model_dir, mp, app_name="CopyStartApp")
        server = start_rec_server(storage, tracing=True)
        try:
            assert post_query(server.port, {"user": "u1", "num": 3})[0] == 200
            with server.service.trace_log._lock:
                trace = list(server.service.trace_log._ring)[-1]
        finally:
            server.stop()
    spans = [(name, start, start + dur)
             for name, _, _, start, dur in trace.spans()]
    _check_span_tree(spans, programs=1)
    (dispatch,) = [(s, e) for n, s, e in spans
                   if n == "batcher.device_dispatch"]
    inside = [(n, s, e) for n, s, e in spans if n.startswith("dispatch.")]
    assert all(dispatch[0] <= s and e <= dispatch[1] + 1e-6
               for _, s, e in inside)
    got, by_hand = _self_ms(inside, dispatch[1] - dispatch[0])
    assert got == pytest.approx(by_hand) and got >= 0.0
