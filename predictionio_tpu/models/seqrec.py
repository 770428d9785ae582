"""Self-attentive sequential recommendation (SASRec-family) on TPU.

Next-item prediction over per-user event sequences — the neural upgrade
of the reference's e2 MarkovChain (e2/.../engine/MarkovChain.scala:26-84,
top-N transition model): where MarkovChain keeps first-order transition
counts, this trains a causal stack over full session histories.

The stack is built from a configuration: ``SeqRecConfig.block`` names a
kind in ``BLOCKS``, each a module of ``models/blocks/`` that says what
the kind is, where it was published and which reference under
``benchmarks/reference/`` holds it to account. This module holds what
every kind shares: the configuration, the head and the loss, training
and its checkpoints, and the serving program. Shapes are fixed (batch,
max_len): sessions are truncated/left-padded on the host, so there is
exactly one compile per config.
"""

from __future__ import annotations

import dataclasses
import logging
from functools import partial
from typing import Any, Mapping

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from predictionio_tpu.models.blocks import (
    brumby, deepseek_v2, laguna, minicpm_sala, nemotron_h, sasrec)
from predictionio_tpu.models.blocks.common import PAD
# models persisted before the kinds had modules of their own pickle
# these records as predictionio_tpu.models.seqrec.<Name>
from predictionio_tpu.models.blocks.common import YarnScaling  # noqa: F401
from predictionio_tpu.models.blocks.deepseek_v2 import MlaMoeWidths
from predictionio_tpu.models.blocks.laguna import LagunaWidths
from predictionio_tpu.models.blocks.minicpm_sala import SalaWidths
from predictionio_tpu.models.blocks.nemotron_h import NemotronHWidths

logger = logging.getLogger(__name__)

#: the block kinds a configuration can name (``SeqRecConfig.block``)
BLOCKS = {
    "sasrec": sasrec.KIND,
    "brumby": brumby.KIND,
    "deepseek_v2": deepseek_v2.KIND,
    "minicpm_sala": minicpm_sala.KIND,
    "nemotron_h": nemotron_h.KIND,
    "laguna": laguna.KIND,
}


@dataclasses.dataclass(frozen=True)
class SeqRecConfig:
    vocab: int              # number of items + 1 (pad)
    max_len: int = 64
    d_model: int = 64
    n_heads: int = 2
    n_layers: int = 2
    mlp_mult: int = 4
    dtype: Any = jnp.bfloat16
    #: rematerialize each transformer block under grad (jax.checkpoint):
    #: activations are recomputed in the backward pass instead of stored,
    #: trading ~30% FLOPs for O(layers) less HBM — the long-context
    #: training knob alongside the "seq" mesh axis
    remat: bool = False
    #: the kind of block the stack is built from (``BLOCKS``). The
    #: fields below are widths the later kinds read; 0 means "as the
    #: first kind derives it"
    block: str = "sasrec"
    n_kv_heads: int = 0         # 0: one key/value head per query head
    head_dim: int = 0           # 0: d_model // n_heads
    d_ff: int = 0               # 0: mlp_mult * d_model
    rope_theta: float = 1e6
    rms_eps: float = 1e-6
    retention_degree: int = 2
    #: added inside the gate's log-sigmoid: ln 999, so that a zero
    #: projection gives a gate of 0.999 and the state remembers
    gate_init_logit: float = 6.906768
    tie_embeddings: bool = True
    #: the type serving holds the weights in on the device
    #: (templates/sessionrec._as_device_tree); training keeps float32
    param_dtype: Any = jnp.float32
    #: a kind's own widths, named after it; None for the other kinds
    mla_moe: MlaMoeWidths | None = None         # "deepseek_v2"
    sala: SalaWidths | None = None              # "minicpm_sala"
    nemotron_h: NemotronHWidths | None = None
    laguna: LagunaWidths | None = None

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def ff(self) -> int:
        return self.d_ff or self.mlp_mult * self.d_model


def activation_bytes_per_token(cfg: SeqRecConfig) -> int:
    """What one token of a serving program holds at the program's peak,
    estimated from the widths by the kind's own rule
    (``BlockKind.bytes_per_token``)."""
    return BLOCKS[cfg.block].bytes_per_token(cfg)


def kernels(cfg: SeqRecConfig, seq_len: int) -> tuple:
    """The Pallas kernels a serving program over ``seq_len``-long
    histories (:func:`predict_topk_batch`, ``inference=True``) engages:
    the kind's own rule (``BlockKind.kernels``), a function of static
    shapes and the backend, so true of every such program."""
    return BLOCKS[cfg.block].kernels(cfg, seq_len)


def init_params(key: jax.Array, cfg: SeqRecConfig,
                dtype: Any = jnp.float32) -> dict:
    """Parameter pytree of ``cfg.block``'s kind in ``dtype`` (float32
    for training; compute casts to cfg.dtype per-op). Jittable: a
    serving-sized tree is drawn on the device."""
    if cfg.block not in BLOCKS:
        raise ValueError(f"unknown block kind {cfg.block!r} "
                         f"(have {sorted(BLOCKS)})")
    return BLOCKS[cfg.block].init(key, cfg, dtype)


def forward(
    params: Mapping,
    seqs: jax.Array,           # (B, S) int32 item ids, right-padded with PAD
    cfg: SeqRecConfig,
    mesh: Mesh | None = None,
    seq_axis: str = "seq",
    inference: bool = False,
) -> jax.Array:
    """Hidden states (B, S, D) in cfg.dtype from ``cfg.block``'s stack."""
    kind = BLOCKS[cfg.block]
    if not kind.seq_parallel and mesh is not None and \
            seq_axis in mesh.shape and int(mesh.shape[seq_axis]) > 1:
        raise NotImplementedError(
            f"the {cfg.block} block has no sequence-parallel form: use a "
            f"mesh without a {seq_axis!r} axis")
    out = kind.forward(params, seqs, cfg, mesh, seq_axis, inference)
    return out[0] if kind.tally else out


def head_table(params: Mapping) -> jax.Array:
    """(V, D) output projection: the untied head where the stack has
    one, else the item-embedding table."""
    return params["head"] if "head" in params else params["item_emb"]


def logits_from_hidden(params: Mapping, h: jax.Array) -> jax.Array:
    """Output projection (tied unless the stack has a head), f32
    accumulation: (B, S, V)."""
    return jnp.einsum("bsd,vd->bsv", h, head_table(params).astype(h.dtype),
                      preferred_element_type=jnp.float32)


#: flat-path budget for the (B, S, V) f32 logits. Tiling is an
#:  OOM-avoidance mechanism, not a default: the rematerialised scan
#:  recomputes the logits matmul in the backward pass, measured ~18%
#:  slower at the bench shape (371k vs 453k tokens/sec) — so the flat
#:  path stands whenever it plausibly fits HBM and tiling engages only
#:  for genuinely oversized (long-context / huge-vocab) configs
_LOSS_TILE_BYTES = 4 << 30


def _pick_loss_tile(b: int, s: int, v: int) -> int | None:
    """Largest divisor of ``s`` whose (b, T, v) f32 logits fit the tile
    budget; None when even the flat path fits (no tiling needed)."""
    if b * s * v * 4 <= _LOSS_TILE_BYTES:
        return None
    for t in (128, 64, 32, 16, 8, 4, 2, 1):
        if s % t == 0 and b * t * v * 4 <= _LOSS_TILE_BYTES:
            return t
    return 1


def next_item_loss(
    params: Mapping,
    seqs: jax.Array,     # (B, S) inputs
    targets: jax.Array,  # (B, S) next item per position, PAD=ignore
    cfg: SeqRecConfig,
    mesh: Mesh | None = None,
) -> jax.Array:
    """Mean masked softmax cross-entropy of next-item prediction.

    Big-vocab configs compute the loss in sequence tiles
    (rematerialised scan): peak logits memory drops from O(B*S*V) to
    O(B*T*V) with the backward pass recomputing per-tile logits.
    Tiling is skipped only when the sequence dim is actually sharded
    (a mesh "seq" axis) — re-tiling a sharded axis would force
    gathers; a data-only mesh leaves S unsharded, so tiling is safe
    and still needed for huge vocabularies. The budget check uses the
    global batch (conservative under data sharding)."""
    h = forward(params, seqs, cfg, mesh)
    seq_sharded = mesh is not None and "seq" in mesh.shape \
        and int(mesh.shape["seq"]) > 1
    tile = None if seq_sharded else _pick_loss_tile(
        h.shape[0], h.shape[1], head_table(params).shape[0])
    tmask = (targets != PAD).astype(jnp.float32)
    if tile is None:
        logits = logits_from_hidden(params, h)         # (B, S, V) f32
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        return jnp.sum(nll * tmask) / jnp.maximum(jnp.sum(tmask), 1.0)

    B, S, D = h.shape
    n = S // tile
    h_t = h.reshape(B, n, tile, D).transpose(1, 0, 2, 3)
    tg_t = targets.reshape(B, n, tile).transpose(1, 0, 2)
    m_t = tmask.reshape(B, n, tile).transpose(1, 0, 2)

    def body(acc, xs):
        ht, tt, mt = xs
        logits = logits_from_hidden(params, ht)        # (B, T, V) f32
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, tt[..., None], axis=-1)[..., 0]
        return acc + jnp.sum(nll * mt), None

    total, _ = jax.lax.scan(
        jax.checkpoint(body), jnp.zeros((), jnp.float32), (h_t, tg_t, m_t))
    return total / jnp.maximum(jnp.sum(tmask), 1.0)


def _adam_update(params, grads, m, v, step, lr, b1=0.9, b2=0.999, eps=1e-8):
    m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
    bc1 = 1 - b1 ** step
    bc2 = 1 - b2 ** step
    params = jax.tree.map(
        lambda p, mm, vv: p - lr * (mm / bc1) / (jnp.sqrt(vv / bc2) + eps),
        params, m, v,
    )
    return params, m, v


def make_train_step(cfg: SeqRecConfig, mesh: Mesh | None = None):
    """One jitted Adam step. Under a mesh, batch shards over "data" and
    (when present) sequence over "seq"; parameters stay replicated and
    XLA inserts the gradient psums over ICI."""

    def step_fn(params, opt_m, opt_v, step, seqs, targets, lr):
        loss, grads = jax.value_and_grad(next_item_loss)(
            params, seqs, targets, cfg, mesh)
        params, opt_m, opt_v = _adam_update(
            params, grads, opt_m, opt_v, step, lr)
        return params, opt_m, opt_v, loss

    if mesh is not None:
        batch_spec = P("data", "seq") if "seq" in mesh.shape else P("data")
        rep = NamedSharding(mesh, P())
        data_sh = NamedSharding(mesh, batch_spec)
        return jax.jit(
            step_fn,
            in_shardings=(rep, rep, rep, None, data_sh, data_sh, None),
            out_shardings=(rep, rep, rep, None),
        )
    return jax.jit(step_fn)


def pad_sequences(
    sequences: list[list[int]], max_len: int
) -> tuple[np.ndarray, np.ndarray]:
    """Keep each sequence's most recent max_len+1 items and produce
    (inputs, targets): inputs are seq[:-1] right-padded with PAD,
    targets the shifted next items."""
    B = len(sequences)
    inputs = np.zeros((B, max_len), dtype=np.int32)
    targets = np.zeros((B, max_len), dtype=np.int32)
    for i, seq in enumerate(sequences):
        seq = seq[-(max_len + 1):]
        ins, tgt = seq[:-1], seq[1:]
        inputs[i, : len(ins)] = ins
        targets[i, : len(tgt)] = tgt
    return inputs, targets


def train(
    sequences: list[list[int]],
    cfg: SeqRecConfig,
    *,
    epochs: int = 20,
    batch_size: int = 64,
    lr: float = 1e-3,
    seed: int = 0,
    mesh: Mesh | None = None,
    checkpoint_dir: str | None = None,
    checkpoint_every: int = 0,
) -> dict:
    """Full training loop over dense-indexed item sequences (ids >= 1).

    Mid-training checkpoint/resume (beyond the reference, whose
    persistence is model-level only — SURVEY.md §5): with
    ``checkpoint_dir`` + ``checkpoint_every`` N, the full training state
    (params, Adam moments, epoch counter) is written atomically every N
    epochs, and a later call with the same dir/config resumes from the
    last completed checkpoint instead of epoch 0."""
    inputs, targets = pad_sequences(sequences, cfg.max_len)
    n = inputs.shape[0]
    # checkpoint identity from the PRE-batch-padding arrays, so a resume
    # after a batch_size or mesh-topology change still *loads* (the
    # fingerprint matches). The continuation is exact only for unchanged
    # batch/mesh: the replayed rng.permutation stream and the restored
    # Adam step counter are batch-size-dependent, so a changed batch_size
    # yields valid training but a different data order/step alignment
    fingerprint = (
        _train_fingerprint(cfg, inputs, targets, lr, seed)
        if checkpoint_dir else None
    )
    # static batch shape: pad the set so every step uses the same compile
    bs = min(batch_size, n)
    if mesh is not None:
        mult = int(mesh.shape.get("data", 1))
        bs = max(mult, (bs // mult) * mult)
    pad_rows = (-n) % bs
    if pad_rows:
        inputs = np.concatenate([inputs, np.zeros((pad_rows, cfg.max_len),
                                                  np.int32)])
        targets = np.concatenate([targets, np.zeros((pad_rows, cfg.max_len),
                                                    np.int32)])
        n = inputs.shape[0]

    key = jax.random.PRNGKey(seed)
    params = init_params(key, cfg)
    opt_m = jax.tree.map(jnp.zeros_like, params)
    opt_v = jax.tree.map(jnp.zeros_like, params)
    start_epoch, it = 0, 0
    if checkpoint_dir:
        resumed = _load_train_state(checkpoint_dir, params, fingerprint)
        if resumed is not None:
            params, opt_m, opt_v, start_epoch, it = resumed
            logger.info("seqrec: resumed from %s at epoch %d",
                        checkpoint_dir, start_epoch)
            if start_epoch >= epochs:
                logger.warning(
                    "seqrec: checkpoint already at epoch %d >= requested "
                    "epochs %d — returning checkpointed weights with no "
                    "further training", start_epoch, epochs)
    step = make_train_step(cfg, mesh)

    rng = np.random.default_rng(seed)
    for epoch in range(epochs):
        if epoch < start_epoch:
            rng.permutation(n)  # keep the data order stream aligned
            continue
        order = rng.permutation(n)
        losses = []
        for s in range(0, n, bs):
            idx = order[s : s + bs]
            it += 1
            params, opt_m, opt_v, loss = step(
                params, opt_m, opt_v, it,
                jnp.asarray(inputs[idx]), jnp.asarray(targets[idx]),
                jnp.float32(lr),
            )
            losses.append(loss)
        if epoch == 0 or (epoch + 1) % 5 == 0:
            logger.info("seqrec epoch %d loss %.4f", epoch + 1,
                        float(jnp.mean(jnp.stack(losses))))
        if checkpoint_dir and checkpoint_every and \
                (epoch + 1) % checkpoint_every == 0:
            _save_train_state(checkpoint_dir, params, opt_m, opt_v,
                              epoch + 1, it, fingerprint)
    return params


# ---------------------------------------------------------------------------
# Mid-training checkpoint state (atomic flat-npz; resume-safe)
# ---------------------------------------------------------------------------


def _flat_paths(tree) -> dict:
    import jax.tree_util as jtu

    leaves = jtu.tree_flatten_with_path(tree)[0]
    return {jtu.keystr(path): leaf for path, leaf in leaves}


def _train_fingerprint(cfg, inputs, targets, lr, seed) -> str:
    """Identity of a training run: config (incl. n_heads/remat, which leaf
    shapes can't distinguish) + the exact dataset + lr/seed. A checkpoint
    only resumes a run with the same fingerprint — a new fold split,
    fresh events, or changed architecture starts fresh instead of
    silently reusing stale weights."""
    import hashlib

    h = hashlib.sha1()
    h.update(repr(dataclasses.asdict(cfg)).encode())
    h.update(np.ascontiguousarray(inputs).tobytes())
    h.update(np.ascontiguousarray(targets).tobytes())
    h.update(np.float64(lr).tobytes())  # pio: lint-ignore[dtype-discipline]: checkpoint-identity serialization — 8 stable bytes, never a compute dtype
    h.update(np.int64(seed).tobytes())
    return h.hexdigest()


def _save_train_state(directory, params, opt_m, opt_v, epoch, it,
                      fingerprint) -> None:
    import os as _os

    _os.makedirs(directory, exist_ok=True)
    arrays = {"__epoch__": np.int64(epoch), "__it__": np.int64(it),
              "__fingerprint__": np.bytes_(fingerprint.encode())}
    for prefix, tree in (("p", params), ("m", opt_m), ("v", opt_v)):
        for path, leaf in _flat_paths(tree).items():
            arrays[f"{prefix}{path}"] = np.asarray(leaf)
    tmp = _os.path.join(directory, ".train_state.npz.tmp")
    final = _os.path.join(directory, "train_state.npz")
    with open(tmp, "wb") as f:
        np.savez(f, **arrays)
    # ONE atomic replace covers params+moments+epoch counter together;
    # a crash can never leave weights and epoch out of step
    _os.replace(tmp, final)


def _load_train_state(directory, template_params, fingerprint):
    """(params, opt_m, opt_v, epoch, it) or None when absent/mismatched."""
    import os as _os

    state_path = _os.path.join(directory, "train_state.npz")
    if not _os.path.exists(state_path):
        return None
    data = np.load(state_path)
    paths = _flat_paths(template_params)
    try:
        import jax.tree_util as jtu

        saved_fp = bytes(data["__fingerprint__"]).decode()
        if saved_fp != fingerprint:
            raise KeyError("__fingerprint__")
        # key paths AND shapes must match the template — belt and braces
        # on top of the fingerprint
        for p, leaf in paths.items():
            if data[f"p{p}"].shape != np.shape(leaf):
                raise KeyError(p)

        def rebuild(prefix):
            flat = {p: jnp.asarray(data[f"{prefix}{p}"]) for p in paths}
            leaves_paths = jtu.tree_flatten_with_path(template_params)[0]
            treedef = jtu.tree_structure(template_params)
            return jtu.tree_unflatten(
                treedef, [flat[jtu.keystr(p)] for p, _ in leaves_paths])

        params = rebuild("p")
        opt_m = rebuild("m")
        opt_v = rebuild("v")
        epoch = int(data["__epoch__"])
        it = int(data["__it__"])
    except KeyError:
        logger.warning("seqrec: checkpoint at %s is from a different "
                       "run (config, dataset, lr, or seed changed); "
                       "starting fresh", directory)
        return None
    return params, opt_m, opt_v, epoch, it


@partial(jax.jit, static_argnames=("k", "cfg"))
def predict_topk_batch(
    params: Mapping, history: jax.Array, k: int, cfg: SeqRecConfig,
    vocab_masks: jax.Array
) -> tuple[jax.Array, ...]:
    """Like :func:`predict_topk` but with a per-query additive logit mask
    ``vocab_masks`` (B, V) — the batched eval path, where each query
    carries its own seen/black-list exclusions. A kind whose program
    counts on the device (``BlockKind.tally``) returns its int32 arrays
    after scores and ids, as its ``forward`` returns them: the selection
    at every position last where the kind has ``BlockKind.choices``."""
    mask = (history != PAD)
    last = jnp.maximum(jnp.sum(mask, axis=1) - 1, 0)
    kind = BLOCKS[cfg.block]
    h = kind.forward(params, history, cfg, None, "seq", True)
    h, *counted = h if kind.tally else (h,)
    hl = jnp.take_along_axis(h, last[:, None, None], axis=1)[:, 0]
    logits = jnp.einsum("bd,vd->bv", hl, head_table(params).astype(h.dtype),
                        preferred_element_type=jnp.float32)
    logits = logits + vocab_masks
    return (*jax.lax.top_k(logits, k), *counted)


def predict_topk(
    params: Mapping, history: jax.Array, k: int, cfg: SeqRecConfig,
    vocab_mask: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Top-k next items for (B, S) histories (the serving hot path; one
    compile per (shape, k, cfg)). ``vocab_mask`` (V,) f32 is added to
    the logits — 0 for allowed ids, a large negative for pad/seen/
    disallowed ids. Thin wrapper over :func:`predict_topk_batch` (the
    (1, V) mask broadcasts), so both paths share one kernel."""
    return predict_topk_batch(params, history, k, cfg,
                              vocab_mask[None, :])[:2]
