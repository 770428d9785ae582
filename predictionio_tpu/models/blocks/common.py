"""What two or more of the session engine's block kinds share: the
padding id, the seam a kind fills (:class:`BlockKind`), the norm and
the dense feed-forward layer, the init draws, a widths record read from
``engine.json``, and the routed-experts layer with its kernels and its
counters."""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Mapping

import jax
import jax.numpy as jnp

from predictionio_tpu.ops import qk_norm

PAD = 0  # item id 0 is reserved for padding; real ids start at 1


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """``rope_scaling`` of type "yarn", keys as published."""
    factor: float = 40.0
    original_max_position_embeddings: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 0.707
    mscale_all_dim: float = 0.707


@dataclasses.dataclass(frozen=True)
class BlockKind:
    init: Any       # (key, cfg, dtype) -> parameter pytree
    #: (params, seqs, cfg, mesh, seq_axis, inference) -> hidden; a kind
    #: with a ``tally`` returns (hidden, *int32 arrays of what the
    #: program counted on the device)
    forward: Any
    #: cfg -> bytes one token of a serving program holds at its peak
    bytes_per_token: Any
    #: (cfg, seq_len) -> names of the Pallas kernels a serving program
    #: over histories that long engages, by the rules the forward pass
    #: itself applies (static shape and backend); "gmm_transpose_rhs"
    #: beside "gmm" where it reads an expert weight transposed
    kernels: Any = lambda cfg, seq_len: ()
    #: None, or (cfg, tokens of the program, *those arrays on the host)
    #: -> {field of templates/sessionrec.SeqDispatch: what to add}: the
    #: one road by which a kind's program reports counters beside scores
    #: and ids
    tally: Any = None
    #: whether the last of those arrays is the selection the program
    #: made at every position (the experts chosen, (expert layers, B, S,
    #: top_k)): no counter, so the server leaves it on the device and
    #: ``tally`` is not given it. The benchmark's reference reads it
    #: from the program that is served, to resolve its router's near
    #: ties the way that program did
    choices: bool = False
    #: whether ``forward`` runs over a mesh whose sequence axis is split
    #: (ring attention); a kind without that form is refused such a mesh
    seq_parallel: bool = False


def widths_of(cls, obj):
    """The widths record ``cls`` of a kind (frozen and hashable: held in
    one field by ``SeqRecConfig`` and by the session template's
    ``AlgorithmParams``) from what ``engine.json`` carries (a JSON object
    whose lists become tuples and whose objects become
    :class:`YarnScaling`, of ``type`` "yarn" where one is given), from
    itself, or None."""
    if obj is None or isinstance(obj, cls):
        return obj
    obj = dict(obj)
    for key, value in obj.items():
        if isinstance(value, list):
            obj[key] = tuple(value)
        elif isinstance(value, Mapping):
            value = dict(value)
            if value.pop("type", "yarn") != "yarn":
                raise ValueError(f"{key}: only type 'yarn' is known")
            obj[key] = YarnScaling(**value)
    return cls(**obj)


def rms(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    return qk_norm.normed(x, w, eps).astype(x.dtype)


def ffn(h, w, dt, activation=jax.nn.silu):
    """A dense feed-forward layer: SwiGLU where it has a gate
    (``activation(h w_gate) * h w_up``), else ``activation(h w_up)``, in
    float32, then ``w_down``."""
    if "w_gate" in w:
        gate = (h @ w["w_gate"].astype(dt)).astype(jnp.float32)
        up = (h @ w["w_up"].astype(dt)).astype(jnp.float32)
        hidden = activation(gate) * up
    else:
        hidden = activation((h @ w["w_up"].astype(dt)).astype(jnp.float32))
    return hidden.astype(dt) @ w["w_down"].astype(dt)


def dense(key, dtype, *shape):
    """A matrix (or a stack of them) normal / sqrt(fan-in), drawn in
    float32 and rounded once."""
    return (jax.random.normal(key, shape, dtype=jnp.float32)
            / math.sqrt(shape[-2])).astype(dtype)


def stack(key, cfg, dtype, draws: int, head: bool = True):
    """What the kinds but SASRec draw first: the item table and, where
    ``head``, an untied head (both normal / sqrt(d_model)), the last
    norm and no layers yet; and ``draws`` keys for each layer."""
    keys = jax.random.split(key, 2 + cfg.n_layers)

    def table(k):
        return (jax.random.normal(k, (cfg.vocab, cfg.d_model),
                                  dtype=jnp.float32)
                / math.sqrt(cfg.d_model)).astype(dtype)

    params = {"item_emb": table(keys[0]),
              "out_norm": jnp.ones((cfg.d_model,), dtype), "layers": []}
    if head:
        params["head"] = table(keys[1])
    return params, [jax.random.split(k, draws) for k in keys[2:]]


def swiglu(keys, dtype, d, ff, *lead):
    """A SwiGLU's three matrices ``ff`` wide, a stack of ``lead`` of
    them for routed experts."""
    return {"w_gate": dense(keys[0], dtype, *lead, d, ff),
            "w_up": dense(keys[1], dtype, *lead, d, ff),
            "w_down": dense(keys[2], dtype, *lead, ff, d)}


def residual_and_ff_bytes(cfg) -> int:
    """The residual stream twice and the widest dense layer's two
    projections (SwiGLU's gate and up; twice the MLP's hidden for
    SASRec), in cfg.dtype. The compiled brumby program at the published
    widths holds 91 KB a token; this says 90."""
    return 2 * (cfg.d_model + cfg.ff) * jnp.dtype(cfg.dtype).itemsize


def routed(h, layer, first: int, *, inference: bool,
           activation=jax.nn.silu, **route):
    """A routed-experts layer over ``h`` (B, S, D): each token routed by
    ``layer["router"]`` (``ops/moe.route``'s arguments ``route``, and
    the layer's ``router_bias`` where it has one), the held experts from
    ``first`` on over their tokens, the shared expert beside them
    (:func:`ffn`, the same ``activation``), summed in float32. Returns
    (y in h's type, ((held,) assignments per held expert, the experts
    each position chose (B, S, top_k))): this chip's experts' part, the
    rest left out (ops/moe.py)."""
    # Pallas and the grouped matmul are imported with the first program
    # that routes, not with the module
    from predictionio_tpu.ops import moe

    B, S, _ = h.shape
    dt = h.dtype
    tokens = h.reshape(B * S, -1)
    with jax.named_scope("moe.route"):
        ids, weights, _ = moe.route(tokens, layer["router"],
                                    bias=layer.get("router_bias"), **route)
    with jax.named_scope("moe.experts"):
        ex = layer["experts"]
        y, counts = moe.routed_experts(
            tokens, ids, weights, ex.get("w_gate"), ex["w_up"],
            ex["w_down"], first, inference=inference, activation=activation)
    with jax.named_scope("moe.shared"):
        shared = ffn(h, layer["shared"], dt, activation)
    y = y.reshape(B, S, -1) + shared.astype(jnp.float32)
    return y.astype(dt), (counts, ids.reshape(B, S, -1))


def stack_routed(per_layer, held: int, shape) -> tuple:
    """Each expert layer's (assignments, choices) stacked over the
    layers: (expert layers, held) and (expert layers, *shape); of no
    layer where the stack has none."""
    if per_layer:
        return tuple(jnp.stack(a) for a in zip(*per_layer))
    return (jnp.zeros((0, held), jnp.int32),
            jnp.zeros((0, *shape), jnp.int32))


def routed_kernels(cfg, ffn_width: int) -> tuple:
    """A serving program's routed layers' kernels (``ops/moe``'s rules)
    over experts ``ffn_width`` wide: ``gmm``; "gmm_transpose_rhs" where
    it reads an expert weight in the order the TPU holds it, as its
    transpose; ``moe_rows`` where the rows are whole lanes."""
    from predictionio_tpu.ops import moe

    kernel = "compiled" if moe.uses_kernel(True) else None
    D = cfg.d_model
    return (("gmm",) if kernel else ()) \
        + (("gmm_transpose_rhs",) if moe.reads_transposed(kernel, D, ffn_width)
           or moe.reads_transposed(kernel, ffn_width, D) else ()) \
        + (("moe_rows",) if moe.moves_rows_by_dma(kernel, D) else ())


def tally_routed(cfg, tokens: int, per_expert) -> dict:
    """The routed layers of one program: assignments to held experts
    over all expert layers, the tokens routed (once, not per layer) and
    the fullest (layer, expert)'s assignments."""
    return {"moe_assignments": int(per_expert.sum()), "moe_tokens": tokens,
            "moe_max_expert_load": int(per_expert.max(initial=0))}
