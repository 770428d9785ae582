"""What decides ``correct`` in a session-engine serve cell whose
configuration names its reference (``harness/seq_ref_data.reference``):
answers the timed path returned, recomputed by that plain reference on
the same device, at the served sizes, from the served weights, and held
to it by ``seq_check.check_one``'s rules under this module's limits.

A reference that routes tokens (``resolutions``) yields the logits
under the resolutions of the last position's router near ties, best
first; the answer has to agree, whole, with one of the first
``MAX_STEPS`` search steps' worth of them.
"""

from __future__ import annotations

import json

import numpy as np

from benchmarks.harness import seq_check, seq_ref_data, traffic as tr

SAMPLE = seq_check.SAMPLE
#: |returned score - reference logit of that item|, in logits (~N(0, 1)
#: over the catalog, the top ten between 3.5 and 5). Four times
#: ``seq_check``'s limits: beside the bfloat16 rounding of every
#: activation through five layers, a router near tie at a position
#: *before* the last may fall the other way in the served path, and only
#: the last position's are resolved. The limit lies between two readings
#: on the chip (PERF.md section 6, PR 31), three times above the one and
#: six times under the other: the served path's worst over 77 checked
#: queries of 20 seeds, 0.132, and the reference with both operands of
#: every product rounded to 8 bits (float8 e4m3), whose own top ten are
#: off by 2.61 to 4.11 and so come out as not correct
SCORE_TOL = 0.4
#: a returned item may rank below the reference's tenth, and a reference
#: top-ten item may be missing, only if its reference logit is this
#: close to the tenth's: two scores' worth (the worst seen is 0.222)
RANK_TOL = 2 * SCORE_TOL
#: two router scores of the last position are a near tie when the ``ln``
#: of their ratio is under this: the served path's bfloat16 activations
#: have turned ties of margins up to 0.029 (PERF.md section 6, PR 31);
#: a chosen group or expert that close to an excluded one may have been
#: exchanged for it
NEAR_TIE = 0.1
#: search steps (one layer of one row each, ~0.15 s on the chip) the
#: reference may spend on one answer
MAX_STEPS = 400


def check_one(ref_logits: np.ndarray, history: np.ndarray, answer: list,
              num: int) -> tuple[str | None, dict]:
    """``seq_check.check_one`` under this module's limits: what has to
    hold whatever the precision (the count, no item twice, no PAD, no
    item of the history, descending scores) is its own verdict."""
    why, worst = seq_check.check_one(ref_logits, history, answer, num)
    if why is None or not why.startswith(("score off", "top-")):
        return why, worst
    ids = np.asarray([ix for ix, _ in answer], np.int64)
    scores = np.asarray([s for _, s in answer], np.float64)
    allowed = ref_logits.copy()
    allowed[0] = -np.inf
    allowed[history] = -np.inf
    order = np.argsort(-allowed, kind="stable")
    tenth = allowed[order[len(ids) - 1]]
    missed = np.setdiff1d(order[:len(ids)], ids)
    worst = {"score_diff": float(np.max(np.abs(scores - ref_logits[ids]))),
             "rank_gap": max(float(np.max(tenth - allowed[ids])), 0.0, float(
                 np.max(allowed[missed] - tenth)) if len(missed) else 0.0)}
    if worst["score_diff"] > SCORE_TOL:
        return f"score off by {worst['score_diff']:.4f}", worst
    if worst["rank_gap"] > RANK_TOL:
        return f"top-{len(ids)} differs by {worst['rank_gap']:.4f}", worst
    return None, worst


def hold_to_resolutions(resolutions, history, answer, num):
    """``resolutions``: the reference's (logits, margin given up), best
    first. Returns (what is wrong or None, the worst differences, how
    many were tried, the margin given up by the one that agreed or -1):
    of the first resolution the answer agrees with, else of the one
    whose scores it comes nearest."""
    nearest, tried = None, 0
    for logits, cost in resolutions:
        tried += 1
        why, worst = check_one(np.asarray(logits, np.float32), history,
                               answer, num)
        if why is None:
            return None, worst, tried, float(cost)
        if nearest is None or worst["score_diff"] < nearest[1]["score_diff"]:
            nearest = (why, worst)
    return (*nearest, tried, -1.0)


def check_answers(rec: dict, model, histories: np.ndarray, pool,
                  config: dict, num: int, seed: int):
    """(correct, problems, notes). Every unknown user's answer must be
    empty; ``SAMPLE`` seeded answered queries of known users are
    recomputed by the reference."""
    reference = seq_ref_data.reference(config)
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
    tried_most, given_up = 0, 0.0
    for k in sample:
        u = int(pool[rec["ix"][k]])
        try:
            answer = [(int(s["item"][1:]) + 1, float(s["score"]))
                      for s in json.loads(rec["body"][k])["itemScores"]]
        except (ValueError, KeyError, TypeError) as exc:
            problems.append(f"malformed answer: {exc}")
            continue
        why, found, tried, cost = hold_to_resolutions(
            reference.resolutions(model.device_tree, histories[u], config,
                                  near_tie=NEAR_TIE, max_steps=MAX_STEPS),
            histories[u], answer, num)
        worst = {key: max(worst[key], found[key]) for key in worst}
        tried_most, given_up = max(tried_most, tried), max(given_up, cost)
        if why:
            problems.append(f"u{u}: {why} ({tried} resolutions)")
    notes = {"checked": int(len(sample)),
             "score_diff_max": worst["score_diff"], "score_tol": SCORE_TOL,
             "rank_gap_max": worst["rank_gap"], "rank_tol": RANK_TOL,
             "near_tie": NEAR_TIE, "resolutions_tried_most": int(tried_most),
             "margin_given_up_most": given_up}
    return not problems and len(sample) > 0, problems[:5], notes
