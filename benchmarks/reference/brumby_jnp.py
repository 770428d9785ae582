"""Plain reference of the Brumby-14B-Base block stack as the session
engine serves it: the logits of a history's last position.

``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``,
the **quadratic** form of power retention (every query row against every
earlier key: no chunks, no state, no cache, no batching). It shares no
code with ``predictionio_tpu.models`` or ``predictionio_tpu.ops``; it
takes the weights that are served, whatever type they are held in, and
upcasts them a matrix at a time, and it works a layer and a block of
query rows at a time so that it fits beside the served copy.

Per layer, ``x`` (S, hidden)::

    h  = rmsnorm(x, in_norm)
    q  = h wq -> (S, heads, d)   k = h wk, v = h wv -> (S, kv_heads, d)
    q, k = rmsnorm over d (q_norm, k_norm), then rope(theta), positions 0..S-1
    lg = log_sigmoid(h wg + gate_init_logit) -> (S, kv_heads)
    w[t, i] = exp(sum_{j=i+1..t} lg[j]) * (q[t] . k[i] / sqrt(d))**2,  t >= i
    y[t] = sum_i w[t, i] v[i] / (sum_i w[t, i] + eps)
    x  = x + concat(y) wo
    x  = x + (silu(h2 w_gate) * (h2 w_up)) w_down,  h2 = rmsnorm(x, post_norm)

then the final RMSNorm and ``x[last] head^T``.

Departures from the published description, each because the public
``config.json`` has no key for it (the configuration file lists them
under ``assumed``):

- degree 2; one gate per key/value head; the output normalised by the
  sum of the weights plus ``eps``; RMSNorm on each head of q and k (the
  parent family's QK-norm);
- ``gate_init_logit`` inside the log-sigmoid (ln 999: a zero projection
  gives a gate of 0.999). Without it seeded weights give gates near
  0.45, a memory of two events;
- the last layer is worked for the last position only: its other rows
  feed nothing that is returned.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
#: None: every product takes float32 operands (the reference). A lower
#: type here (tools/seq_precision.py sets float8) rounds both operands of
#: every product through it: the reading that has to come out as not
#: correct. Set it with set_operands(), which drops compiled programs
_OPERANDS = None


def set_operands(dtype) -> None:
    global _OPERANDS
    _OPERANDS = dtype
    jax.clear_caches()


def _op(x):
    x = x.astype(F32)
    return x if _OPERANDS is None else x.astype(_OPERANDS).astype(F32)


def widths(config: dict) -> dict:
    """The numbers the forward pass reads from a configuration file."""
    return {"heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "d": config["head_dim"],
            "eps": float(config["rms_norm_eps"]),
            "theta": float(config["rope_theta"]),
            "gate_init_logit": float(config["gate_init_logit"]),
            "retention_eps": float(config["retention_eps"])}


def rmsnorm(x, w, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(F32)


def rope(x, positions, theta):
    """Rotate (n, heads, d) by position: pairs (i, i + d/2), frequency
    theta**(-2i/d), all d dimensions (the parent family's convention)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = positions.astype(F32)[:, None] * inv[None, :]        # (n, d/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


@jax.jit
def _project(x, in_norm, w, eps):
    return _op(rmsnorm(x, in_norm, eps)) @ _op(w)


def _retention_rows(q, k, v, cum, rows, eps):
    """Quadratic power retention for the query positions ``rows``
    against every key: q (r, heads, d) at those rows; k, v (S, kv, d);
    cum (S, kv) inclusive cumulative log-gates."""
    r, heads, d = q.shape
    kv = k.shape[1]
    qg = _op(q).reshape(r, kv, heads // kv, d)
    s = jnp.einsum("tgad,igd->gati", qg, _op(k)) / math.sqrt(d)  # (kv, a, r, S)
    gap = cum[rows][:, None, :] - cum[None, :, :]              # (r, S, kv)
    seen = rows[:, None] >= jnp.arange(k.shape[0])[None, :]    # (r, S)
    decay = jnp.exp(jnp.where(seen[..., None], gap, -jnp.inf))
    w = s * s * decay.transpose(2, 0, 1)[:, None]              # (kv, a, r, S)
    w = _op(w)
    num = jnp.einsum("gati,igd->tgad", w, _op(v))
    den = jnp.sum(w, axis=-1).transpose(2, 0, 1)[..., None]    # (r, kv, a, 1)
    return (num / (den + eps)).reshape(r, heads * d)


_retention_rows_jit = jax.jit(_retention_rows)


@jax.jit
def _mlp(x, post_norm, w_gate, w_up, w_down, eps):
    h = _op(rmsnorm(x, post_norm, eps))
    return x + _op(jax.nn.silu(h @ _op(w_gate)) * (h @ _op(w_up))) \
        @ _op(w_down)


def layer(x, w, cfg, rows, row_block: int, mlp_block: int):
    """One block. ``x`` (S, hidden) float32; returns the new ``x`` at the
    positions ``rows`` only (all of them unless this is the last layer)."""
    S = x.shape[0]
    heads, kv, d, eps = cfg["heads"], cfg["kv_heads"], cfg["d"], cfg["eps"]
    pos = jnp.arange(S)
    q = _project(x, w["in_norm"], w["wq"], eps).reshape(S, heads, d)
    k = _project(x, w["in_norm"], w["wk"], eps).reshape(S, kv, d)
    v = _project(x, w["in_norm"], w["wv"], eps).reshape(S, kv, d)
    q = rope(rmsnorm(q, w["q_norm"], eps), pos, cfg["theta"])
    k = rope(rmsnorm(k, w["k_norm"], eps), pos, cfg["theta"])
    lg = jax.nn.log_sigmoid(_project(x, w["in_norm"], w["wg"], eps)
                            + cfg["gate_init_logit"])
    cum = jnp.cumsum(lg, axis=0)
    out = []
    for lo in range(0, len(rows), row_block):
        part = rows[lo:lo + row_block]
        y = _retention_rows_jit(q[part], k, v, cum, part,
                                cfg["retention_eps"])
        out.append(x[part] + _op(y) @ _op(w["wo"]))
    x = jnp.concatenate(out, axis=0)
    return jnp.concatenate(
        [_mlp(x[lo:lo + mlp_block], w["post_norm"], w["w_gate"], w["w_up"],
              w["w_down"], eps) for lo in range(0, x.shape[0], mlp_block)],
        axis=0)


def hidden_last(weights, history, config: dict, row_block: int = 256,
                mlp_block: int = 2048):
    """Final-norm hidden state (hidden,) at the last of ``history``'s
    positions (a 1-D array of item indices, no padding)."""
    cfg = widths(config)
    with jax.default_matmul_precision("highest"):
        x = jnp.take(weights["item_emb"], jnp.asarray(history), axis=0
                     ).astype(F32)
        S = x.shape[0]
        layers = weights["layers"]
        for n, w in enumerate(layers):
            last = n == len(layers) - 1
            rows = jnp.arange(S - 1, S) if last else jnp.arange(S)
            x = layer(x, w, cfg, rows, row_block, mlp_block)
        return rmsnorm(x[-1], weights["out_norm"], cfg["eps"])


def last_logits(weights, history, config: dict, row_block: int = 256,
                mlp_block: int = 2048, vocab_block: int = 32768):
    """Logits (vocabulary,) of the position after ``history``."""
    h = hidden_last(weights, history, config, row_block, mlp_block)
    head = weights["head"] if "head" in weights else weights["item_emb"]
    with jax.default_matmul_precision("highest"):
        return jnp.concatenate(
            [_op(head[lo:lo + vocab_block]) @ _op(h)
             for lo in range(0, head.shape[0], vocab_block)])


def retention_quadratic(q, k, v, log_g, eps: float = 1e-6):
    """The mixing alone, all rows at once: q (S, heads, d), k and v
    (S, kv_heads, d), log_g (S, kv_heads) -> (S, heads, d). What the
    tests hold ``ops.retention`` and the state equations against."""
    S, heads, d = q.shape
    with jax.default_matmul_precision("highest"):
        cum = jnp.cumsum(log_g.astype(F32), axis=0)
        return _retention_rows(q.astype(F32), k.astype(F32), v.astype(F32),
                               cum, jnp.arange(S), eps).reshape(S, heads, d)
