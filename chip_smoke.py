#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives, on one TPU, exactly what an operator runs, each step a child
process of a parent that never imports JAX (a chip belongs to one
process at a time, so at no moment do two of them hold JAX):

1. storage inside the checkout, ``pio status`` (names the device),
   ``pio app new``;
2. ``rate`` events seeded through ``Events.insert_batch`` over the full
   MovieLens-20M catalog (138,493 users x 26,744 items, a
   power-law shape, every user and item present so the factor tables
   have their full width);
3. ``pio train --profile`` of the recommendation template at rank 32,
   default fused layout;
4. ``pio deploy --batching``, sequential ``POST /queries.json`` (known
   user, unknown user, a ``num`` off the top-k menu), one burst of 8
   concurrent queries, ``GET /metrics``, ``pio undeploy``;
5. ``flash_attention`` compiled (never interpreted) against
   ``full_attention`` at both ends of the envelope the dispatcher
   declares, at the sessionrec serving shape; retention's fused state
   pass against the ``jax.numpy`` step, and the fused way in (QK-norm,
   RoPE, chunk order) against ``ops/qk_norm.prepare``'s; the flat
   top-k's two-stage selection against one ``lax.top_k`` at the Books
   cell's shape and over a catalog 100 items longer; the
   ``deepseek_v2`` kind's tiled
   attention core (S 8,192, 128 heads, 192/128) and grouped product (40
   experts x 307 rows) against their plain forms.

It passes only on a TPU: any other platform, any failed child or any
malformed answer is a non-zero exit with the child's last lines, and no
result line. On success the last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

    python3 chip_smoke.py [--events N]      # 20,000,000 = ML-20M, by hand
"""

from __future__ import annotations

import argparse
import concurrent.futures
import functools
import json
import math
import os
import re
import shutil
import signal
import statistics
import subprocess
import sys
import time
import urllib.error
import urllib.request

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(ROOT, ".chip_smoke")        # git-ignored
PIO = os.path.join(ROOT, "bin", "pio")
USERS, ITEMS, RANK, ITERATIONS = 138_493, 26_744, 32, 3
#: sized to the 1200 s contract with room for a cold compile; the
#: catalog and the rank are the full ones at any count
DEFAULT_EVENTS = 2_000_000
APP = "ChipSmoke"
PORT = 18431
#: flash vs XLA in bf16: both accumulate in f32 and round the output to
#: bf16 (8 bits of mantissa), so they may differ by an ulp of the value:
#: |diff| <= FLASH_TOL * (1 + |reference|)
FLASH_TOL = 2e-2
#: the fused state pass against the jax.numpy step: bfloat16 outputs a
#: last bit apart (2^-8 of the value) where a float32 sum fell otherwise
RETENTION_TOL = 1e-2


class SmokeFailure(Exception):
    pass


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


# ---------------------------------------------------------------------------
# the data: one generator for the seeding child and the checking parent
# ---------------------------------------------------------------------------

def make_ratings(n: int, seed: int = 0):
    """``n`` (user, item, rating) triples: first one rating for every
    user and item (so both factor tables reach full width whatever
    ``n``), then a power-law draw."""
    if n < USERS:
        raise SmokeFailure(f"--events must be at least {USERS} to cover "
                           "the catalog")
    rng = np.random.default_rng(seed)
    cover_u = np.arange(USERS, dtype=np.int32)
    cover_i = (cover_u % ITEMS).astype(np.int32)
    m = n - USERS
    users = (USERS * rng.random(m) ** 1.8).astype(np.int32)
    items = (ITEMS * rng.random(m) ** 1.8).astype(np.int32)
    vals = rng.integers(1, 11, size=n).astype(np.float32) / 2.0
    return (np.concatenate([cover_u, users]),
            np.concatenate([cover_i, items]), vals)


def seed_child(n: int, app_id: int) -> int:
    """Runs in a child (JAX-free): write the events through the storage
    layer's bulk path and report what wrote them."""
    from datetime import datetime, timedelta, timezone

    from predictionio_tpu.core.datamap import DataMap
    from predictionio_tpu.core.event import Event
    from predictionio_tpu.storage.registry import Storage

    users, items, vals = make_ratings(n)
    events = Storage.default().get_events()
    events.init(app_id)
    t0 = datetime(2020, 1, 1, tzinfo=timezone.utc)
    batch = 5000
    for lo in range(0, n, batch):
        hi = min(n, lo + batch)
        events.insert_batch([
            Event(event="rate", entity_type="user", entity_id=f"u{u}",
                  target_entity_type="item", target_entity_id=f"i{i}",
                  properties=DataMap({"rating": float(v)}),
                  event_time=t0 + timedelta(seconds=k))
            for k, u, i, v in zip(range(lo, hi), users[lo:hi].tolist(),
                                  items[lo:hi].tolist(),
                                  vals[lo:hi].tolist())
        ], app_id)
    print(f"seeded {n}")
    return 0


# ---------------------------------------------------------------------------
# the kernel phase (its own JAX child)
# ---------------------------------------------------------------------------

def kernels_child() -> int:
    """Runs in a child: flash_attention COMPILED vs full_attention at
    the envelope ends, sessionrec serving shape (d_model 256, 4 heads ->
    D = 64, bf16, causal); then power retention with its state pass in
    the fused kernel vs the jax.numpy step, at the smallest eligible
    shape and at the session cell's."""
    from predictionio_tpu.utils.accelerator import start_compute

    start_compute()
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops import pallas_attention as pa
    from predictionio_tpu.ops.attention import full_attention

    if pa._mode() != "compiled":
        print(f"flash_attention mode is {pa._mode()!r}, not 'compiled'")
        return 1
    ok = True
    for s in (pa._MIN_SEQ, pa._MAX_SEQ):
        q, k, v = (jax.random.normal(key, (1, 4, s, 64), jnp.bfloat16)
                   for key in jax.random.split(jax.random.PRNGKey(s), 3))
        lowered = pa._flash_call.lower(
            q, k, v, jnp.ones((1, s), jnp.float32), True, False)
        if "tpu_custom_call" not in lowered.as_text():
            print(f"S={s}: no Mosaic custom call in the lowered program")
            return 1
        t0 = time.perf_counter()
        got = jax.block_until_ready(pa.flash_attention(q, k, v, causal=True))
        t1 = time.perf_counter()
        want = jax.block_until_ready(full_attention(q, k, v, causal=True))
        got32, want32 = got.astype(jnp.float32), want.astype(jnp.float32)
        err = float(jnp.max(jnp.abs(got32 - want32)))
        finite = bool(jnp.all(jnp.isfinite(got32)))
        good = finite and got.shape == want.shape and bool(jnp.all(
            jnp.abs(got32 - want32) <= FLASH_TOL * (1 + jnp.abs(want32))))
        ok &= good
        print(f"flash S={s}: max|diff|={err:.3e} finite={finite} "
              f"first_call_s={t1 - t0:.2f} {'ok' if good else 'MISMATCH'}")
    return 0 if (ok and retention_kernel_ok() and qk_norm_kernel_ok()
                 and two_stage_topk_ok() and mla_kernels_ok()
                 and sparse_kernels_ok()) else 1


def sparse_kernels_ok() -> bool:
    """The ``minicpm_sala`` kind's two kernels at its cell's shape (S
    32,768, 32 query heads over 2 key/value heads of 128), each against
    its plain form on the last 256 positions: block scores from
    compressed keys, and attention over the blocks the positions kept."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops import sparse_attention as sa

    s, G, R, d, tail = 32768, 2, 16, 128, 256
    sz = sa.SparseSizes()
    if not sa.uses_kernel(s, True, sz, d, R):
        print("sparse: the rule does not choose the kernels")
        return False
    keys = jax.random.split(jax.random.PRNGKey(s), 3)
    q = (jax.random.normal(keys[0], (1, s, G * R * d)) * 4 / d ** 0.5
         ).astype(jnp.bfloat16)
    k, v = (jax.random.normal(key, (1, s, G * d)).astype(jnp.bfloat16)
            for key in keys[1:])
    pos = jnp.arange(s)
    t0 = time.perf_counter()
    scores = jax.block_until_ready(sa.selection_scores(q, k, sz=sz, groups=G))
    t1 = time.perf_counter()
    want = sa.block_scores(
        q.reshape(1, s, G, R, d)[:, -tail:],
        sa.compressed_keys(k.reshape(1, s, G, d), sz), pos[-tail:], sz,
        sa.n_blocks(s, sz))
    err = float(jnp.max(jnp.abs(scores[:, :, -tail:] - want)))
    ok = err < 1e-2                      # sums of 16 heads' probabilities
    print(f"sparse selection S={s}: max|diff|={err:.3e} "
          f"first_call_s={t1 - t0:.2f} {'ok' if ok else 'MISMATCH'}")
    kept = sa.select_blocks(scores, pos, sz)
    out, counts, _ = jax.jit(
        lambda q, k, v: sa.attend(q, k, v, sz, groups=G, inference=True))(
            q, k, v)
    heads = (1, s, G, R, d)
    sc = jnp.einsum("btgrd,bsgd->bgrts", q.reshape(heads)[:, -tail:],
                    k.reshape(1, s, G, d), preferred_element_type=jnp.float32)
    seen = (pos[None, :] <= pos[-tail:, None]) & jnp.repeat(
        kept[:, :, -tail:], sz.block_size, axis=-1)[:, :, None]
    p = jax.nn.softmax(jnp.where(seen, sc, -1e30), axis=-1)
    plain = jnp.einsum("bgrts,bsgd->btgrd", p.astype(v.dtype),
                       v.reshape(1, s, G, d),
                       preferred_element_type=jnp.float32)
    err = float(jnp.max(jnp.abs(out[:, -tail:].astype(jnp.float32)
                                - plain.reshape(1, tail, -1))))
    good = bool(jnp.all(jnp.isfinite(out.astype(jnp.float32)))) and \
        err <= FLASH_TOL * 4 and int(counts[0]) == s * G
    print(f"sparse attention S={s}: max|diff|={err:.3e} rows, blocks, "
          f"key blocks scored {counts.tolist()} "
          f"{'ok' if good else 'MISMATCH'}")
    return ok and good


def mla_kernels_ok() -> bool:
    """The ``deepseek_v2`` kind's two kernels at its cell's shape, each
    against its plain form: the tiled attention core (S 8,192, 128 heads,
    query/key 128 + 64, value 128; 8 of the heads are compared, the plain
    form's logits for all would not fit) and the grouped product at 40
    experts x 307 rows inside the 49,152-row bound."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops import mla_attention as mla, moe

    s, heads, dn, dr, dv = 8192, 128, 128, 64, 128
    if not (mla.uses_kernel(s, True, dn, dr, dv, heads)
            and moe.uses_kernel(inference=True)):
        print("mla: the rules do not choose the kernels")
        return False
    keys = jax.random.split(jax.random.PRNGKey(s), 8)

    def draw(key, *shape):
        return jax.random.normal(key, shape, jnp.bfloat16)

    qn, qp = draw(keys[0], 1, s, heads * dn), draw(keys[1], 1, s, heads * dr)
    kv, kp = draw(keys[2], 1, s, heads * (dn + dv)), draw(keys[3], 1, s, dr)
    scale = mla.softmax_scale(dn + dr)
    t0 = time.perf_counter()
    got = jax.block_until_ready(mla.attend(
        qn, qp, kv, kp, heads=heads, dn=dn, dv=dv, scale=scale,
        inference=True))
    t1 = time.perf_counter()
    some = 8                    # the plain form's logits for all would not fit
    want = mla.attend(qn[..., :some * dn], qp[..., :some * dr],
                      kv[..., :some * (dn + dv)], kp, heads=some, dn=dn,
                      dv=dv, scale=scale, inference=False)
    got32 = got[..., :some * dv].astype(jnp.float32)
    err = float(jnp.max(jnp.abs(got32 - want.astype(jnp.float32))))
    ok = bool(jnp.all(jnp.isfinite(got.astype(jnp.float32)))) and \
        err <= FLASH_TOL * 4
    print(f"mla attention S={s} H={heads} 192/128: max|diff|={err:.3e} "
          f"first_call_s={t1 - t0:.2f} {'ok' if ok else 'MISMATCH'}")
    del qn, qp, kv, kp, got, want

    rows, experts = 49152, 40
    x = draw(keys[5], rows, 5120)
    w = draw(keys[6], experts, 5120, 1536) * 0.02
    sizes = jnp.full((experts,), 307, jnp.int32)
    t0 = time.perf_counter()
    got = jax.block_until_ready(
        moe._grouped(x, w, sizes, "compiled", jnp.float32))[:experts * 307]
    t1 = time.perf_counter()
    want = jax.lax.ragged_dot(x[:experts * 307], w, sizes,
                              preferred_element_type=jnp.float32)
    err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    good = err < 1e-2
    print(f"mla grouped product {experts} x 307 rows of {rows}: "
          f"max rel diff={err:.3e} first_call_s={t1 - t0:.2f} "
          f"{'ok' if good else 'MISMATCH'}")
    return ok and good


def retention_kernel_ok() -> bool:
    """Both ends of ``pallas_retention.in_envelope`` that anything runs:
    one head of 128 over two chunks of 128, and the session cell's 40
    and 8 heads over three chunks of 256 (the carried state read twice).
    The two paths round the same products and differ by the order of
    float32 sums: a last bit of bfloat16 here and there."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops import retention

    ok = True
    for name, (s, h, g, chunk) in (("smallest", (256, 1, 1, 128)),
                                   ("cell", (768, 40, 8, 256))):
        if not retention.fuses_state_pass(128, h // g, chunk, inference=True):
            print(f"retention {name}: the rule does not choose the kernel")
            return False
        keys = jax.random.split(jax.random.PRNGKey(s), 4)
        q, k, v = (jax.random.normal(key, (1, s, n, 128), jnp.bfloat16)
                   for key, n in zip(keys, (h, g, g)))
        log_g = jax.nn.log_sigmoid(
            6.9 + jax.random.normal(keys[3], (1, s, g), jnp.float32))
        fused, plain = (
            jax.jit(lambda *a, inference=inference: retention.power_retention(
                *a, chunk=chunk, inference=inference))
            for inference in (True, False))
        if "tpu_custom_call" not in fused.lower(q, k, v, log_g).as_text():
            print(f"retention {name}: no Mosaic custom call in the program")
            return False
        t0 = time.perf_counter()
        got = jax.block_until_ready(fused(q, k, v, log_g)).astype(jnp.float32)
        t1 = time.perf_counter()
        want = plain(q, k, v, log_g).astype(jnp.float32)
        err = float(jnp.max(jnp.abs(got - want)))
        good = bool(jnp.all(jnp.isfinite(got))) and bool(jnp.all(
            jnp.abs(got - want) <= RETENTION_TOL * (1 + jnp.abs(want))))
        ok &= good
        print(f"retention {name} S={s} H={h} G={g} chunk={chunk}: "
              f"max|diff|={err:.3e} first_call_s={t1 - t0:.2f} "
              f"{'ok' if good else 'MISMATCH'}")
    return ok


def qk_norm_kernel_ok() -> bool:
    """The fused way in (``ops/pallas_qk_norm.py``) against the
    ``jax.numpy`` form compiled by XLA at the widths the session cells
    run, three chunks of 256: MiniCPM-SALA's lightning q (32 heads),
    Brumby's q (40 heads, a step of 128 rows; its ``jax.numpy`` form
    writes a rounding after the norm, which XLA's TPU backend does not
    perform, and the kernel has none) in retention's chunk order, and
    the sparse layers' q token-major without positions. Same equations:
    equal bit for bit when PR 37 read them; the tolerance is a last bit
    of bfloat16."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops import qk_norm, retention

    ok, s, d = True, 768, 128
    rope = qk_norm.rope_tables(s, d, 10000.0)
    for name, heads, how in (
            ("lightning q", 32, dict(rope=rope, chunk=256)),
            ("brumby q", 40, dict(rope=rope, chunk=256,
                                  norm_dtype=jnp.bfloat16)),
            ("sparse q", 32, dict(scale=d ** -0.5))):
        chunk = how.pop("chunk", None)
        if not qk_norm.fuses(d, heads * d, chunk or s, inference=True):
            print(f"qk_norm {name}: the rule does not choose the kernel")
            return False
        kx, kw = jax.random.split(jax.random.PRNGKey(heads))
        x = jax.random.normal(kx, (2, s, heads * d), jnp.bfloat16)
        w = (2 + 0.1 * jax.random.normal(kw, (d,))).astype(jnp.bfloat16)
        t0 = time.perf_counter()
        got = jax.block_until_ready(qk_norm.fused(
            x, w, heads=heads, eps=1e-6, chunk=chunk,
            **{k: v for k, v in how.items() if k != "norm_dtype"}))
        t1 = time.perf_counter()
        want = jax.jit(lambda x, w, how=how, heads=heads: qk_norm.prepare(
            x, w, heads=heads, eps=1e-6, **how))(x, w)
        if chunk:
            want = retention.chunk_order(want, chunk)
        got, want = got.astype(jnp.float32), want.astype(jnp.float32)
        err = float(jnp.max(jnp.abs(got - want)))
        good = got.shape == want.shape and bool(jnp.all(
            jnp.isfinite(got))) and bool(jnp.all(
                jnp.abs(got - want) <= RETENTION_TOL * (1 + jnp.abs(want))))
        ok &= good
        print(f"qk_norm {name} S={s} heads={heads} chunk={chunk}: "
              f"max|diff|={err:.3e} equal={float(jnp.mean(got == want)):.4f} "
              f"first_call_s={t1 - t0:.2f} {'ok' if good else 'MISMATCH'}")
    return ok


def two_stage_topk_ok() -> bool:
    """The flat path's two-stage selection at the Books cell's shape
    (4.4M items, rank 128, ``num`` 10) through ``ALSModel.batch_topk``
    at B = 1, 4, 8, and at B = 4 over a catalog 100 items longer (a
    tail short of a group): answers equal one ``lax.top_k`` over the
    same masked scores on every finite slot, both read from the
    model's bfloat16 serving copy of the item table, and the model's
    observer counts every dispatch (the rule the counter and the
    program share)."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.models.als import ALSModel
    from predictionio_tpu.ops import topk
    from predictionio_tpu.utils.bimap import EntityIdIxMap

    rank, users, k = 128, 64, 10
    # the program this repo ran before the two-stage selection
    single = jax.jit(lambda table, rows, *a: jax.lax.top_k(
        topk._masked_scores(table[rows], *a), k))
    rng = np.random.default_rng(30)
    ok = True
    for items, batches in ((4_400_000, (1, 4, 8)), (4_400_100, (4,))):
        ku, ki = jax.random.split(jax.random.PRNGKey(30))
        model = ALSModel(
            rank=rank,
            user_factors=jax.random.normal(ku, (users, rank), jnp.float32),
            item_factors=jax.random.normal(ki, (items, rank), jnp.float32),
            user_ids=EntityIdIxMap.from_ids([f"u{i}" for i in range(users)]),
            item_ids=EntityIdIxMap.from_ids([]), seen_by_user={})
        counted = []
        model.set_topk_observer(lambda: counted.append(1))
        allow = jnp.ones((items,), jnp.float32)
        for n, b in enumerate(batches, start=1):
            uixs = rng.integers(0, users, b).astype(np.int32)
            cols = rng.integers(0, items, (b, 8)).astype(np.int32)
            cols[:, 0] = items - 1                  # in the tail, where one is
            mask = (rng.random((b, 8)) < 0.7).astype(np.float32)
            t0 = time.perf_counter()
            vals, idxs = (np.asarray(a) for a in
                          model.batch_topk(uixs, cols, mask, None, k))
            t1 = time.perf_counter()
            served = model.serving_item_factors()
            want_v, want_i = (np.asarray(a) for a in single(
                model.user_factors, uixs, served, cols, mask, allow))
            finite = np.isfinite(want_v)
            good = (np.array_equal(vals, want_v) and bool(finite.all())
                    and served.dtype == jnp.bfloat16
                    and np.array_equal(idxs[finite], want_i[finite])
                    and len(counted) == n)
            ok &= good
            print(f"topk two-stage I={items} B={b}: groups of "
                  f"{topk.two_stage_group_width(items, k)}, counted "
                  f"{len(counted)} of {n} dispatches "
                  f"first_call_s={t1 - t0:.2f} "
                  f"{'ok' if good else 'MISMATCH'}")
        del model, allow
    return ok


# ---------------------------------------------------------------------------
# the parent
# ---------------------------------------------------------------------------

def child_env() -> dict:
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("PIO_")}
    env.update({
        "PYTHONPATH": ROOT,
        "PYTHONUNBUFFERED": "1",
        "PIO_STORAGE_REPOSITORIES_METADATA_NAME": "pio_meta",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "PIO_SQLITE",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_NAME": "pio_event",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "PIO_BIN",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_NAME": "pio_model",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "PIO_FS",
        "PIO_STORAGE_SOURCES_PIO_SQLITE_TYPE": "sqlite",
        "PIO_STORAGE_SOURCES_PIO_SQLITE_PATH": os.path.join(WORK, "pio.db"),
        "PIO_STORAGE_SOURCES_PIO_BIN_TYPE": "binevents",
        "PIO_STORAGE_SOURCES_PIO_BIN_PATH": os.path.join(WORK, "events"),
        "PIO_STORAGE_SOURCES_PIO_FS_TYPE": "localfs",
        "PIO_STORAGE_SOURCES_PIO_FS_PATH": os.path.join(WORK, "models"),
        "PIO_FS_BASEDIR": WORK,
    })
    return env


def run(label: str, argv: list[str], timeout: float) -> tuple[str, float]:
    """Run one child to its end; (stdout, seconds). A non-zero exit or
    a timeout is the smoke's failure, with the child's last lines."""
    log(f"{label}: {' '.join(argv[:6])}{' ...' if len(argv) > 6 else ''}")
    t0 = time.perf_counter()
    try:
        p = subprocess.run(argv, cwd=WORK, env=child_env(), text=True,
                           capture_output=True, timeout=timeout)
    except FileNotFoundError as exc:
        raise SmokeFailure(f"{label}: {exc}") from None
    except subprocess.TimeoutExpired as exc:
        tail = ((exc.stdout or b"")[-2000:], (exc.stderr or b"")[-2000:])
        raise SmokeFailure(
            f"{label}: no exit within {timeout:.0f}s\n{tail}") from None
    secs = time.perf_counter() - t0
    if p.returncode != 0:
        raise SmokeFailure(
            f"{label}: exit {p.returncode} after {secs:.1f}s\n"
            f"--- stdout\n{p.stdout[-3000:]}\n--- stderr\n{p.stderr[-3000:]}")
    return p.stdout, secs


def http(method: str, path: str, body: dict | None = None,
         timeout: float = 120.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{PORT}{path}", data=data, method=method,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.read().decode()


def check_answer(label: str, answer: dict, num: int,
                 seen: set[str] | None) -> None:
    """``num`` finite, descending scores over distinct unseen items —
    or, for a user training never saw, the empty answer."""
    scores = answer.get("itemScores")
    if not isinstance(scores, list):
        raise SmokeFailure(f"{label}: no itemScores in {answer}")
    if seen is None:
        if scores:
            raise SmokeFailure(f"{label}: unknown user got {scores[:3]}")
        return
    vals = [s["score"] for s in scores]
    names = [s["item"] for s in scores]
    if len(scores) != num:
        raise SmokeFailure(f"{label}: {len(scores)} scores, wanted {num}")
    if not all(isinstance(v, float) and math.isfinite(v) for v in vals):
        raise SmokeFailure(f"{label}: non-finite score in {vals}")
    if any(a < b for a, b in zip(vals, vals[1:])):
        raise SmokeFailure(f"{label}: scores not descending: {vals}")
    if len(set(names)) != num or not all(
            re.fullmatch(r"i\d+", n) and int(n[1:]) < ITEMS for n in names):
        raise SmokeFailure(f"{label}: bad item ids {names}")
    if seen & set(names):
        raise SmokeFailure(
            f"{label}: recommended seen items {sorted(seen & set(names))}")


def serve_and_query(users: np.ndarray, items: np.ndarray) -> dict:
    """Step 4: deploy, query, scrape, undeploy. The server is a child of
    this process and is gone when this returns, whatever happened."""
    @functools.cache
    def seen_of(u: int) -> set[str]:
        return {f"i{i}" for i in items[users == u].tolist()}

    log(f"deploy: pio deploy --batching --port {PORT}")
    with open(os.path.join(WORK, "deploy.log"), "w") as logf:
        server = subprocess.Popen(
            [PIO, "deploy", "--batching", "--ip", "127.0.0.1",
             "--port", str(PORT)],
            cwd=WORK, env=child_env(), stdout=logf,
            stderr=subprocess.STDOUT, start_new_session=True)

    def server_tail() -> str:
        with open(os.path.join(WORK, "deploy.log")) as f:
            return f.read()[-3000:]

    try:
        t0 = time.perf_counter()
        while True:
            if server.poll() is not None:
                raise SmokeFailure(
                    f"deploy: exit {server.returncode}\n{server_tail()}")
            try:
                http("GET", "/", timeout=5)
                break
            except (urllib.error.URLError, OSError):
                if time.perf_counter() - t0 > 420:
                    raise SmokeFailure(
                        f"deploy: not ready in 420s\n{server_tail()}")
                time.sleep(0.5)
        ready_s = time.perf_counter() - t0

        first_calls: dict[str, float] = {}

        def query(label, user, num, seen):
            t = time.perf_counter()
            answer = json.loads(http(
                "POST", "/queries.json", {"user": user, "num": num}))
            ms = (time.perf_counter() - t) * 1e3
            check_answer(label, answer, num, seen)
            first_calls.setdefault(label, round(ms, 1))
            return ms

        # the heaviest user (index 0 under the power law) and a light one
        first_ms = query("known user", "u0", 10, seen_of(0))
        query("light user", f"u{USERS - 1}", 10, seen_of(USERS - 1))
        query("unknown user", "nobody", 10, None)
        # 7 is on no top-k menu (ops/topk._K_WIDTHS): served from the
        # k=10 program, trimmed
        query("off-menu num", "u1", 7, seen_of(1))
        query("num 100", "u2", 100, seen_of(2))
        def burst() -> float:
            t = time.perf_counter()
            with concurrent.futures.ThreadPoolExecutor(8) as pool:
                for f in [pool.submit(query, "burst", f"u{u}", 10,
                                      seen_of(u))
                          for u in (3, 5, 8, 13, 21, 34, 55, 89)]:
                    f.result()
            return (time.perf_counter() - t) * 1e3

        burst_first_ms, burst_warm_ms = burst(), burst()
        warm = [query("warm", "u0", 10, seen_of(0)) for _ in range(20)]

        metrics = http("GET", "/metrics")
        in_use = {
            m.group(1): float(m.group(2)) for m in re.finditer(
                r'^pio_device_bytes_in_use\{device="([^"]+)"[^}]*\} (\S+)$',
                metrics, re.M)}
        if not in_use.get("tpu:0", 0) > 0:
            raise SmokeFailure(
                "/metrics: no pio_device_bytes_in_use{device=\"tpu:0\"} > 0 "
                f"(have {in_use})")
        stats = json.loads(http("GET", "/stats.json"))
        run("undeploy", [PIO, "undeploy", "--ip", "127.0.0.1",
                         "--port", str(PORT)], 60)
        try:
            server.wait(timeout=60)
        except subprocess.TimeoutExpired:
            raise SmokeFailure("deploy: still alive 60s after undeploy")
        return {
            "ready_s": ready_s, "first_ms": first_ms,
            "first_calls_ms": first_calls,
            "warm_p50_ms": statistics.median(warm),
            "warm_max_ms": max(warm),
            "burst_first_ms": burst_first_ms, "burst_warm_ms": burst_warm_ms,
            "bytes_in_use": in_use,
            "batching": stats.get("batching"),
        }
    finally:
        if server.poll() is None:
            os.killpg(server.pid, signal.SIGKILL)
            server.wait()


def cache_entries(path: str) -> int:
    try:
        return sum(1 for name in os.listdir(path)
                   if not name.endswith("-atime"))
    except FileNotFoundError:
        return 0


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--events", type=int, default=DEFAULT_EVENTS,
                        help="ratings to seed (the catalog and rank stay "
                             f"full; default {DEFAULT_EVENTS:,})")
    args = parser.parse_args()
    t_start = time.perf_counter()
    me = [sys.executable, os.path.abspath(__file__)]

    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)

    # 1. storage + device
    status, _ = run("status", [PIO, "status"], 300)
    m = re.search(r"JAX devices: platform=(\S+) device_kind='([^']*)' "
                  r"count=(\d+)", status)
    if not m:
        raise SmokeFailure(f"status: no device line in\n{status[-2000:]}")
    platform, kind, count = m.group(1), m.group(2), int(m.group(3))
    if platform != "tpu":
        raise SmokeFailure(
            f"found platform {platform!r} ({kind!r} x{count}), not a TPU: "
            "this smoke proves the chip path and passes nowhere else")
    native = re.search(r"Native components: eventlog=(\S+) packer=(\S+)",
                       status)
    log(f"device: platform={platform} device_kind={kind!r} count={count}")
    log(f"native: eventlog={native.group(1)} packer={native.group(2)}")
    out, _ = run("app new", [PIO, "app", "new", APP], 120)
    app_id = int(re.search(r"ID: (\d+)", out).group(1))

    # 2. events
    _, seed_s = run("seed", me + ["_seed", str(args.events), str(app_id)],
                    3600)
    log(f"seed: {args.events:,} events over {USERS:,} users x {ITEMS:,} "
        f"items in {seed_s:.1f}s ({args.events / seed_s:,.0f} ev/s)")

    # 3. train
    with open(os.path.join(WORK, "engine.json"), "w") as f:
        json.dump({
            "id": "chip-smoke",
            "engineFactory":
                "predictionio_tpu.templates.recommendation.engine_factory",
            "datasource": {"params": {"app_name": APP}},
            "algorithms": [{"name": "als", "params": {
                "rank": RANK, "numIterations": ITERATIONS,
                "lambda": 0.08, "seed": 3}}],
        }, f)
    out, train_wall = run("train", [PIO, "train", "--profile"], 3600)
    if "(COMPLETED)" not in out:
        raise SmokeFailure(f"train: no COMPLETED instance in\n{out[-2000:]}")
    with open(os.path.join(WORK, "TRAIN_REPORT.json")) as f:
        report = json.load(f)
    if ("tpu" not in report["deviceKind"].lower()
            or report["deviceCount"] != count
            or report["hbm"]["peakBytes"] is None):
        raise SmokeFailure(
            "TRAIN_REPORT.json: deviceKind "
            f"{report['deviceKind']!r}, deviceCount {report['deviceCount']}, "
            f"hbm.peakBytes {report['hbm']['peakBytes']} — wanted a TPU "
            f"kind, {count} device(s) and a measured peak")
    stages = {k: v["wallSeconds"] for k, v in report["stages"].items()}
    log("train: " + " | ".join(f"{k} {v:.1f}s" for k, v in stages.items())
        + f" | process {train_wall:.1f}s")
    log(f"train: compile {report['compile']['totalSeconds']:.1f}s in "
        f"{report['compile']['totalCompiles']} compiles | HBM peak "
        f"{report['hbm']['peakBytes'] / 2**30:.2f} GiB | flops.peakSource "
        f"{report['flops']['peakSource']} | mfu {report['mfu']}")
    backends = set()
    for dirpath, _, names in os.walk(os.path.join(WORK, "models")):
        if "checkpoint_meta.json" in names and "ann" not in dirpath:
            with open(os.path.join(dirpath, "checkpoint_meta.json")) as f:
                backends.add(json.load(f)["backend"])
    log(f"checkpoint backend: {', '.join(sorted(backends)) or 'none found'}")
    if not backends:
        raise SmokeFailure("train: no checkpoint under .chip_smoke/models")

    # 4. serve
    users, items, _ = make_ratings(args.events)
    served = serve_and_query(users, items)
    log(f"deploy: ready in {served['ready_s']:.1f}s | first query "
        f"{served['first_ms']:.1f} ms | warm p50 "
        f"{served['warm_p50_ms']:.2f} ms (max {served['warm_max_ms']:.2f}) "
        f"| burst of 8: first {served['burst_first_ms']:.1f} ms, again "
        f"{served['burst_warm_ms']:.1f} ms")
    log(f"deploy: first call of each query, ms: {served['first_calls_ms']}")
    log(f"deploy: bytes_in_use {served['bytes_in_use']} | batching "
        f"{served['batching']}")

    # 5. kernels
    out, kern_s = run("kernels", me + ["_kernels"], 900)
    for line in out.splitlines():
        if line.startswith(("flash ", "retention ", "topk ", "mla ")):
            log(line)

    # the rule of utils/accelerator, restated so that this count checks it
    cache = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
             or os.path.join(ROOT, ".jax_cache"))
    entries = cache_entries(cache)
    log(f"compile cache: {cache} ({entries} entries)")
    if not entries:
        raise SmokeFailure(f"no compile-cache entry under {cache}")
    log(f"wall: {time.perf_counter() - t_start:.1f}s "
        f"(kernels {kern_s:.1f}s)")
    if "jax" in sys.modules:
        raise SmokeFailure("the smoke's parent imported jax")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": count}}))
    return 0


if __name__ == "__main__":
    if sys.argv[1:2] == ["_seed"]:
        sys.exit(seed_child(int(sys.argv[2]), int(sys.argv[3])))
    if sys.argv[1:2] == ["_kernels"]:
        sys.exit(kernels_child())
    try:
        sys.exit(main())
    except SmokeFailure as failure:
        print(f"[smoke] FAILED: {failure}", file=sys.stderr, flush=True)
        sys.exit(1)
