"""What decides ``correct`` in a session-engine serve cell: answers the
timed path returned, recomputed by the plain reference
(``reference/brumby_jnp.py``) on the same device, at the served sizes,
from the served weights.
"""

from __future__ import annotations

import json

import numpy as np

from benchmarks.harness import traffic as tr
from benchmarks.reference import brumby_jnp

#: answered queries recomputed per run (a reference pass over 16,384
#: events is some seconds of float32 products at six passes each)
SAMPLE = 3
#: |returned score - reference logit of that item|, in logits (the
#: logits of a seeded model are ~N(0, 1) over the catalog, the top ten
#: between 3.7 and 5.5). The served path rounds every activation to
#: bfloat16 (8 bits of mantissa) through four layers and accumulates in
#: float32. The limit lies between two readings on the chip (PERF.md
#: section 6, PR 27): the served path's worst over 45 checked queries of
#: 15 seeds, 0.045, and the reference with both operands of every
#: product rounded to 8 bits (float8 e4m3), whose own top ten are off by
#: 0.51 to 1.01 and so come out as not correct
SCORE_TOL = 0.1
#: a returned item may rank below the reference's tenth, and a
#: reference top-ten item may be missing, only if its reference logit is
#: this close to the tenth's: two scores' worth of the rounding above
#: (the worst seen is 0.032)
RANK_TOL = 2 * SCORE_TOL


def check_one(ref_logits: np.ndarray, history: np.ndarray, answer: list,
              num: int, score_tol: float = SCORE_TOL,
              rank_tol: float = RANK_TOL) -> tuple[str | None, dict]:
    """``answer``: [(dense item index, score)] as returned. Returns
    (what is wrong or None, the worst differences found). What has to
    hold whatever the precision (the count, no item twice, no PAD, no
    item of the history, descending scores) comes first; then both
    differences are taken and held to the limits given."""
    worst = {"score_diff": 0.0, "rank_gap": 0.0}
    allowed = ref_logits.copy()
    allowed[0] = -np.inf                                   # PAD
    allowed[history] = -np.inf
    if len(answer) != min(num, int(np.isfinite(allowed).sum())):
        return f"{len(answer)} items for num {num}", worst
    ids = np.asarray([ix for ix, _ in answer], np.int64)
    scores = np.asarray([s for _, s in answer], np.float64)
    if len(set(ids.tolist())) != len(ids):
        return "an item twice", worst
    if not np.all(np.isfinite(allowed[ids])):
        return "PAD or an item of the history returned", worst
    if np.any(np.diff(scores) > 0):
        return "scores not in descending order", worst
    worst["score_diff"] = float(np.max(np.abs(scores - ref_logits[ids])))
    order = np.argsort(-allowed, kind="stable")
    tenth = allowed[order[len(ids) - 1]]
    below = float(np.max(tenth - allowed[ids]))            # returned, ranked lower
    missed = np.setdiff1d(order[:len(ids)], ids)
    above = float(np.max(allowed[missed] - tenth)) if len(missed) else 0.0
    worst["rank_gap"] = max(below, above, 0.0)
    if worst["score_diff"] > score_tol:
        return f"score off by {worst['score_diff']:.4f}", worst
    if worst["rank_gap"] > rank_tol:
        return f"top-{len(ids)} differs by {worst['rank_gap']:.4f}", worst
    return None, worst


def check_answers(rec: dict, model, histories: np.ndarray, pool,
                  config: dict, num: int, seed: int):
    """(correct, problems, notes). Every unknown user's answer must be
    empty; ``SAMPLE`` seeded answered queries of known users are
    recomputed by the reference."""
    ok = np.flatnonzero(rec["status"] == 200)
    users = pool[rec["ix"][ok]]
    problems = []
    for k in ok[users < 0]:
        if json.loads(rec["body"][k]).get("itemScores") != []:
            problems.append(f"unknown user answered {rec['body'][k][:80]!r}")
    known = ok[users >= 0]
    rng = np.random.default_rng([seed, tr.SAMPLE])
    sample = rng.choice(known, size=min(SAMPLE, len(known)), replace=False)
    worst = {"score_diff": 0.0, "rank_gap": 0.0}
    for k in sample:
        u = int(pool[rec["ix"][k]])
        try:
            answer = [(int(s["item"][1:]) + 1, float(s["score"]))
                      for s in json.loads(rec["body"][k])["itemScores"]]
        except (ValueError, KeyError, TypeError) as exc:
            problems.append(f"malformed answer: {exc}")
            continue
        ref = np.asarray(brumby_jnp.last_logits(
            model.device_tree, histories[u], config), np.float32)
        why, found = check_one(ref, histories[u], answer, num)
        worst = {key: max(worst[key], found[key]) for key in worst}
        if why:
            problems.append(f"u{u}: {why}")
    notes = {"checked": int(len(sample)),
             "score_diff_max": worst["score_diff"], "score_tol": SCORE_TOL,
             "rank_gap_max": worst["rank_gap"], "rank_tol": RANK_TOL}
    return not problems and len(sample) > 0, problems[:5], notes
