"""The grouped products read an expert weight in the order the TPU holds
it (``ops/moe.reads_transposed``): a weight ``(held, D, F)`` whose ``F``
is not a whole number of 128 lanes while ``D`` is is held with ``D``
minor, and ``gmm`` reads it as its transpose instead of copying it whole
into row-major order first. On the CPU (interpret mode) the transposed
read is the read as given bit for bit; at whole-lane widths, and in the
plain form, the rule is the identity: the traced program is the same.
Then the server's gauge of kernels, ``serving.seqKernels``, by the kind's
rule.
``tests/test_retention_kernel.py`` compiles the Nemotron-H cell's widths
for a described v5e (no copy of an expert weight); ``chip_smoke.py``
holds the compiled layer to the read as given on the chip."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.models import seqrec
from predictionio_tpu.obs.registry import serving_collector
from predictionio_tpu.ops import moe, pallas_attention
from predictionio_tpu.templates import sessionrec
from predictionio_tpu.utils.bimap import BiMap
from tests.seq_wiring import wired_stats

#: rows of 256 (whole lanes); experts 208 wide (not whole lanes: held
#: transposed) and 128, 1,024, 1,536 wide (whole lanes: held as given)
D = 256
CASES = [(f, gated, kernel) for f in (208, 128, 1024, 1536)
         for gated in (False, True) for kernel in (None, "interpret")]


def _layer(seed, gated, f, experts=8, held=4, first=2, T=24, top_k=2):
    rng = np.random.default_rng(seed)

    def draw(*shape, scale=1.0):
        return jnp.asarray(rng.standard_normal(shape) * scale, jnp.bfloat16)

    ids = jnp.asarray(np.argsort(rng.random((T, experts)), axis=1)[:, :top_k],
                      jnp.int32)
    weights = jnp.asarray(rng.random((T, top_k)) + 0.25, jnp.float32)
    ex = (draw(held, D, f, scale=D ** -0.5) if gated else None,
          draw(held, D, f, scale=D ** -0.5), draw(held, f, D, scale=f ** -0.5))
    return draw(T, D), ids, weights, ex, first


@pytest.mark.parametrize(
    "f, gated, kernel", CASES,
    ids=[f"f{f}-{'swiglu' if g else 'relu2'}-{k or 'plain'}"
         for f, g, k in CASES])
def test_the_transposed_read_is_the_read_as_given(f, gated, kernel,
                                                  monkeypatch):
    x, ids, weights, ex, first = _layer(f, gated, f)
    act = jax.nn.silu if gated else moe.relu2
    held_transposed = kernel is not None and f % 128 != 0
    assert moe.reads_transposed(kernel, D, f) == held_transposed
    assert not moe.reads_transposed(kernel, f, D)    # w_down: D whole lanes

    def layer():
        return moe.routed_experts(x, ids, weights, *ex, first, kernel=kernel,
                                  activation=act)

    traced = str(jax.make_jaxpr(layer)())
    y, counts = layer()
    monkeypatch.setattr(moe, "reads_transposed", lambda *a: False)
    as_given = str(jax.make_jaxpr(lambda: layer())())   # not the cached trace
    want_y, want_counts = layer()
    assert np.array_equal(np.asarray(y), np.asarray(want_y))
    assert np.array_equal(np.asarray(counts), np.asarray(want_counts))
    assert int(counts.sum()) > 0
    # the weight's two last axes swapped inside the program, or nothing
    assert ("permutation=(0, 2, 1)" in traced) == held_transposed
    assert (traced == as_given) != held_transposed


#: the two kinds' tiny presets at rows of 128 lanes: Nemotron-H's experts
#: 64 wide (not whole lanes), DeepSeek-V2's 128 (whole lanes)
KINDS = {
    "nemotron_h": ("tests.test_nemotron_h_seqrec", "nemotron_h", 64, True),
    "deepseek_v2": ("tests.test_deepseek_v2_seqrec", "mla_moe", 128, False),
}


@pytest.mark.parametrize("kind", list(KINDS))
def test_the_counter_counts_the_programs_that_read_the_experts_as_held(
        kind, monkeypatch):
    """Where the rule says the kernel runs (a compiled TPU backend, the
    widths above), the server's gauge of a Nemotron-H-shaped model lists
    ``gmm_transpose_rhs`` and a DeepSeek-V2-shaped one's does not; on
    the CPU neither does."""
    import importlib

    module_name, key, ffn, as_held = KINDS[kind]
    module = importlib.import_module(module_name)
    params = sessionrec.AlgorithmParams(**{
        **module.PARAMS, "d_model": 128,
        key: {**module.WIDTHS, "moe_intermediate_size": ffn}})
    cfg = params.seqrec_config(vocab=module.ITEMS + 1)
    S = cfg.max_len
    rng = np.random.default_rng(42)
    model = sessionrec.SeqRecEngineModel(
        params=jax.tree.map(np.array, seqrec.init_params(
            jax.random.PRNGKey(42), cfg)), cfg=cfg,
        item_index=BiMap({f"i{k}": k + 1 for k in range(module.ITEMS)}),
        histories={f"u{u}": rng.integers(1, module.ITEMS + 1, size=n)
                   .astype(np.int32) for u, n in enumerate([S, 9, S])})
    algo = sessionrec.SeqRecAlgorithm(params)
    queries = [(i, sessionrec.Query(user=f"u{i}", num=3)) for i in range(3)]
    rule = seqrec.kernels

    def on_a_chip(cfg, seq_len):
        with monkeypatch.context() as m:
            m.setattr(pallas_attention, "_mode", lambda: "compiled")
            return rule(cfg, seq_len)

    assert "gmm_transpose_rhs" not in rule(cfg, S)           # the CPU
    assert ("gmm_transpose_rhs" in on_a_chip(cfg, S)) == as_held
    cpu_stats = wired_stats(algo, model)
    seen = []
    model.set_dispatch_observer(seen.append)
    try:
        algo.batch_predict(model, queries)
        monkeypatch.setattr(seqrec, "kernels", on_a_chip)
        algo.batch_predict(model, queries)
    finally:
        model.set_dispatch_observer(None)
    chip_stats = wired_stats(algo, model)
    cpu, chip = seen
    assert cpu.programs == chip.programs > 0
    # the dispatch's counters do not move with the rule
    assert chip == cpu
    assert cpu_stats.snapshot()["seqKernels"] == []
    snap = chip_stats.snapshot()
    assert ("gmm_transpose_rhs" in snap["seqKernels"]) == as_held
    assert snap["seqKernels"] == sorted(on_a_chip(cfg, S))
    exposed = {m.name: m.samples for m in serving_collector(chip_stats)()}
    assert exposed["pio_serving_seq_kernel"] == [
        ({"kernel": name}, 1.0) for name in snap["seqKernels"]]
