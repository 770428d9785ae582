"""Cases shared by the retention tests of PR 37 (the way in and the way
out): seeded inputs, the parent commit's outputs and gradients for them,
and the parent's formulation of the way in written out — norm, RoPE by
``concatenate``, the cast, ``reshape().transpose()`` — which the new
paths must equal."""

from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32

#: name -> what :func:`inputs` draws; the name is the seed
CASES = {
    "degree2-B2-S80-R2-gate": dict(
        degree=2, B=2, S=80, H=4, G=2, chunk=32, gate="position"),
    "degree2-B1-S96-R1-gate": dict(
        degree=2, B=1, S=96, H=2, G=2, chunk=32, gate="position"),
    "degree1-B2-S70-R2-gate": dict(
        degree=1, B=2, S=70, H=4, G=2, chunk=32, gate="position"),
    "degree1-B1-S64-R1-constant": dict(
        degree=1, B=1, S=64, H=4, G=4, chunk=16, gate="constant"),
    "degree1-B2-S50-R2-constant": dict(
        degree=1, B=2, S=50, H=4, G=2, chunk=16, gate="constant"),
    "degree1-B1-S40-R1-constant-f32": dict(
        degree=1, B=1, S=40, H=2, G=2, chunk=16, gate="constant",
        dtype="float32"),
}

#: name -> (sha256 of ``power_retention``'s output as float32 bytes,
#: sum of |gradient| of ``sum(output * probe)`` by q, k, v and log_g),
#: both of the parent commit (3ae3a70) on the CPU
PARENT = {
    "degree2-B2-S80-R2-gate": (
        "23c0a3b25fc07e1be33e1082d2f29370b9cdab8bb4174817d7150363ce061874",
        (3104.772, 2188.178, 1777.4779, 924.4323)),
    "degree2-B1-S96-R1-gate": (
        "eec79c8b67a3b4f2a2091c265fc413fd75054090a470626488bc285b6c6137a9",
        (803.4138, 801.4816, 698.8875, 469.0848)),
    "degree1-B2-S70-R2-gate": (
        "77af88d89dde4ee601b2ede8f8803f20cb8ef1ff2f713eeaa8a3aacbaf079bf7",
        (21831.5977, 16223.9307, 16027.4873, 11805.0771)),
    "degree1-B1-S64-R1-constant": (
        "34931cae004b02b4413c32d1f82723b1311009a088744bc4ddd3cb110e6704d2",
        (12092.7383, 11919.6152, 11469.0371, 3620.6929)),
    "degree1-B2-S50-R2-constant": (
        "815bc727966480cb4fe6f4403046eeea7e0b6a4d6756eba6091edc01784d6209",
        (18108.1602, 13101.1777, 13009.541, 2425.707)),
    "degree1-B1-S40-R1-constant-f32": (
        "e3e2a5321f315758b7c8d747b6d81d716ad2d63d906b882b3460478486bd64ef",
        (3214.5583, 2942.7334, 3096.7358, 1054.9471)),
}


def inputs(name, degree, B, S, H, G, chunk, gate, dtype="bfloat16", d=16):
    """(q, k, v, log_g) of a case: S not a whole number of chunks in
    some, B = 2 in some, one constant decay a head (``log_g`` (G,)) or a
    gate a position."""
    rng = np.random.default_rng(sum(map(ord, name)))
    dt = jnp.dtype(dtype)
    q = jnp.asarray(rng.standard_normal((B, S, H, d)), dt)
    k, v = (jnp.asarray(rng.standard_normal((B, S, G, d)), dt)
            for _ in range(2))
    shape = (G,) if gate == "constant" else (B, S, G)
    log_g = jnp.asarray(-np.abs(rng.standard_normal(shape)) * 0.05, F32)
    return q, k, v, log_g


def probe(shape):
    return jnp.asarray(np.random.default_rng(5).standard_normal(shape), F32)


def digest(out) -> str:
    return hashlib.sha256(np.asarray(out, np.float32).tobytes()).hexdigest()


def plain_way_in(x, weight, *, heads, eps, scale=1.0, theta=None,
                 norm_dtype=None, chunk=None):
    """The parent's way in, as ``models/seqrec`` and ``ops/retention``
    spelled it: (B, S, heads * d) as projected -> per-head RMS norm in
    float32 (``normed`` / ``_rms``: rounded to ``norm_dtype`` where the
    model's norm returned the stream's type), ``_rope`` (both tables by
    ``concatenate``, the rotated half by a third), one cast, and for
    ``chunk`` the layout by ``reshape().transpose()``."""
    B, S, width = x.shape
    d = width // heads
    x32 = x.reshape(B, S, heads, d).astype(F32)
    y = x32 * (jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                             + eps) * scale) * weight.astype(F32)
    if norm_dtype is not None:
        y = y.astype(norm_dtype)
    if theta is not None:
        inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
        ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
        cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[None, :, None, :]
        sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[None, :, None, :]
        y = y.astype(F32)
        y1, y2 = y[..., :d // 2], y[..., d // 2:]
        y = y * cos + jnp.concatenate([-y2, y1], axis=-1) * sin
    y = y.astype(x.dtype)
    if chunk:
        y = y.reshape(B, S // chunk, chunk, heads, d).transpose(1, 0, 3, 2, 4)
    return y
