"""Ring attention vs full attention numerics on a virtual device mesh."""

from __future__ import annotations

import numpy as np
import pytest

from predictionio_tpu.utils.testing import force_cpu_devices

force_cpu_devices(8)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from predictionio_tpu.ops.attention import (  # noqa: E402
    blockwise_attention,
    full_attention,
    ring_attention,
)

B, H, S, D = 2, 4, 64, 16  # S divides the 8-device seq axis


def _qkv(seed: int = 0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    shape = (B, H, S, D)
    q = jnp.asarray(rng.standard_normal(shape), dtype=dtype)
    k = jnp.asarray(rng.standard_normal(shape), dtype=dtype)
    v = jnp.asarray(rng.standard_normal(shape), dtype=dtype)
    return q, k, v


@pytest.fixture(scope="module")
def seq_mesh():
    devs = np.array(jax.devices()[:8])
    return Mesh(devs, ("seq",))


class TestRingAttention:
    def test_matches_full_causal(self, seq_mesh):
        q, k, v = _qkv()
        expected = full_attention(q, k, v, causal=True)
        got = ring_attention(q, k, v, seq_mesh, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                                   atol=1e-5, rtol=1e-5)

    def test_matches_full_noncausal(self, seq_mesh):
        q, k, v = _qkv(1)
        expected = full_attention(q, k, v, causal=False)
        got = ring_attention(q, k, v, seq_mesh, causal=False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                                   atol=1e-5, rtol=1e-5)

    def test_padding_mask(self, seq_mesh):
        q, k, v = _qkv(2)
        # second sequence only has 40 real positions
        kv_mask = np.ones((B, S), dtype=np.float32)
        kv_mask[1, 40:] = 0.0
        kv_mask = jnp.asarray(kv_mask)
        expected = full_attention(q, k, v, causal=True, kv_mask=kv_mask)
        got = ring_attention(q, k, v, seq_mesh, causal=True, kv_mask=kv_mask)
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                                   atol=1e-5, rtol=1e-5)

    def test_inside_jit_with_sharded_inputs(self, seq_mesh):
        q, k, v = _qkv(3)
        sh = NamedSharding(seq_mesh, P(None, None, "seq", None))
        qs, ks, vs = (jax.device_put(x, sh) for x in (q, k, v))

        @jax.jit
        def run(q, k, v):
            return ring_attention(q, k, v, seq_mesh, causal=True)

        got = run(qs, ks, vs)
        expected = full_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                                   atol=1e-5, rtol=1e-5)

    def test_bf16_inputs_accumulate_f32(self, seq_mesh):
        q, k, v = _qkv(4, dtype=jnp.bfloat16)
        expected = full_attention(q, k, v, causal=True)
        got = ring_attention(q, k, v, seq_mesh, causal=True)
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(got, dtype=np.float32),
            np.asarray(expected, dtype=np.float32),
            atol=3e-2, rtol=3e-2,
        )

    def test_grads_flow(self, seq_mesh):
        q, k, v = _qkv(5)

        def loss_ring(q, k, v):
            return jnp.sum(ring_attention(q, k, v, seq_mesh, causal=True) ** 2)

        def loss_full(q, k, v):
            return jnp.sum(full_attention(q, k, v, causal=True) ** 2)

        g_ring = jax.grad(loss_ring)(q, k, v)
        g_full = jax.grad(loss_full)(q, k, v)
        np.testing.assert_allclose(np.asarray(g_ring), np.asarray(g_full),
                                   atol=1e-4, rtol=1e-4)


class TestBlockwiseAttention:
    """Single-device long-context training path: query-tile scan +
    remat — must match full_attention in values AND gradients."""

    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_full_values(self, causal):
        q, k, v = _qkv(11)
        kv_mask = np.ones((B, S), dtype=np.float32)
        kv_mask[1, 40:] = 0.0
        kv_mask = jnp.asarray(kv_mask)
        exp = full_attention(q, k, v, causal=causal, kv_mask=kv_mask)
        got = blockwise_attention(q, k, v, causal=causal, kv_mask=kv_mask,
                                  q_block=16)
        np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                                   atol=1e-5, rtol=1e-5)

    def test_matches_full_gradients(self):
        q, k, v = _qkv(12)

        def loss_full(q, k, v):
            return jnp.sum(full_attention(q, k, v, causal=True) ** 2)

        def loss_block(q, k, v):
            return jnp.sum(
                blockwise_attention(q, k, v, causal=True, q_block=16) ** 2)

        gf = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
        gb = jax.grad(loss_block, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gb):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       atol=1e-4, rtol=1e-4)

    def test_q_block_must_divide(self):
        q, k, v = _qkv(13)
        with pytest.raises(ValueError, match="divide"):
            blockwise_attention(q, k, v, q_block=48)

    def test_seqrec_training_routes_blockwise_at_long_s(self, monkeypatch):
        """forward() must take the blockwise path at S >= 4096 (stubbed —
        the point is routing; the math is covered above)."""
        from predictionio_tpu.models import seqrec

        calls = []
        monkeypatch.setattr(
            seqrec, "blockwise_attention",
            lambda q, k, v, **kw: calls.append(kw["q_block"]) or q,
        )
        cfg = seqrec.SeqRecConfig(vocab=50, max_len=4096, d_model=8,
                                  n_heads=2, n_layers=1)
        params = seqrec.init_params(jax.random.PRNGKey(0), cfg)
        seqs = jnp.ones((1, 4096), jnp.int32)
        seqrec.forward(params, seqs, cfg)
        # smallest dividing tile: the r5 sweep measured q_block=128
        # 1.8x faster than 512 at S=4096
        assert calls == [128]


class TestPallasFlashAttention:
    """Pallas flash kernel — auto-dispatched for causal compiled-mode
    calls in the measured 2048<=S<=16384 envelope since the round-5
    causal-KV-skip + tile-sweep pass (ops/pallas_attention docstring
    has the A/B table); on the CPU test backend force=True exercises
    it in interpret mode."""

    def test_matches_full_attention(self):
        from predictionio_tpu.ops.pallas_attention import flash_attention

        q, k, v = _qkv(6)
        kv_mask = np.ones((B, S), dtype=np.float32)
        kv_mask[0, 50:] = 0.0
        kv_mask = jnp.asarray(kv_mask)
        for causal in (True, False):
            exp = full_attention(q, k, v, causal=causal, kv_mask=kv_mask)
            got = flash_attention(q, k, v, causal=causal, kv_mask=kv_mask,
                                  force=True)
            np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                                       atol=1e-5, rtol=1e-5)

    def test_default_path_is_xla_on_cpu(self):
        """Interpret mode (CPU backend) never auto-engages — unforced
        calls are exactly full_attention regardless of S."""
        from predictionio_tpu.ops import pallas_attention

        q, k, v = _qkv(7)
        got = pallas_attention.flash_attention(q, k, v, causal=True)
        exp = full_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                                   atol=1e-6, rtol=1e-6)

    def test_auto_dispatch_causal_envelope(self, monkeypatch):
        """r5 dispatch rules: unforced compiled-mode calls engage the
        kernel ONLY for causal attention inside the measured
        2048<=S<=16384 envelope; non-causal and out-of-envelope depths
        fall back; force=True routes anywhere buildable (mode and
        kernel stubbed — no TPU in CI; the point is routing)."""
        from predictionio_tpu.ops import pallas_attention as pa

        calls = []
        monkeypatch.setattr(pa, "_mode", lambda: "compiled")
        monkeypatch.setattr(
            pa, "_flash_call",
            lambda q, k, v, m, causal, interp, *t: calls.append(q.shape) or q,
        )
        # stub the fallback too: at these sizes the real full_attention
        # would materialize (S, S) logits (~4 GB at 32768)
        monkeypatch.setattr(pa, "full_attention",
                            lambda q, k, v, **kw: q)
        for S, causal, expect in (
            (1024, True, 0),      # below the envelope
            (2048, True, 1),      # measured win
            (4096, True, 1),
            (16384, True, 1),     # envelope top
            (32768, True, 0),     # beyond VMEM-resident K/V
            (4096, False, 0),     # non-causal: the KV-skip win is causal-only
        ):
            calls.clear()
            q = jnp.zeros((1, 1, S, 8), jnp.float32)
            pa.flash_attention(q, q, q, causal=causal)
            assert len(calls) == expect, (S, causal)
        for S, expect in ((2048, 1), (16384, 1)):
            calls.clear()
            q = jnp.zeros((1, 1, S, 8), jnp.float32)
            pa.flash_attention(q, q, q, causal=True, force=True)
            assert len(calls) == expect, (S, expect)
        # K/V residency bounds the envelope too (what Mosaic accepted
        # on a v5e): bf16 D=64 reaches the top, f32 D=64 stops at 8192
        for dtype, S, expect in ((jnp.bfloat16, 16384, 1),
                                 (jnp.float32, 8192, 1),
                                 (jnp.float32, 16384, 0)):
            calls.clear()
            q = jnp.zeros((1, 1, S, 64), dtype)
            pa.flash_attention(q, q, q, causal=True)
            assert len(calls) == expect, (dtype, S)

    def test_compiled_kernel_failure_raises(self, monkeypatch):
        """On a TPU a kernel that fails to build or compile raises; it
        never answers with XLA's result (which would hide a broken
        kernel behind a correct one)."""
        from predictionio_tpu.ops import pallas_attention as pa

        def refuse(*args):
            raise RuntimeError("Mosaic refused the kernel")

        monkeypatch.setattr(pa, "_mode", lambda: "compiled")
        monkeypatch.setattr(pa, "_flash_call", refuse)
        monkeypatch.setattr(
            pa, "full_attention",
            lambda *a, **kw: pytest.fail("fell back to full_attention"))
        q = jnp.zeros((1, 1, 2048, 8), jnp.float32)
        with pytest.raises(RuntimeError, match="Mosaic refused"):
            pa.flash_attention(q, q, q, causal=True)
