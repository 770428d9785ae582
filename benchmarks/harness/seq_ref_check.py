"""What decides ``correct`` in a session-engine serve cell whose
configuration names its reference (``harness/seq_ref_data.reference``):
answers the timed path returned, recomputed by that plain reference on
the same device, at the served sizes, from the served weights, and held
to it by ``seq_check.check_one``'s rules under the limits the reference
module carries beside the reasons for them: ``SCORE_TOL`` and
``RANK_TOL``, and where it has near ties to resolve ``NEAR_TIE`` and
``MAX_STEPS``. This module keeps no number of its own.

A reference that has near ties to resolve has ``resolutions``, which
yields the logits under them, best first; the answer has to agree,
whole, with one of the first ``MAX_STEPS`` search steps' worth of them.
One that has none has ``last_logits`` alone, and that is its one
resolution.
"""

from __future__ import annotations

import json

import numpy as np

from benchmarks.harness import seq_check, seq_ref_data, traffic as tr

SAMPLE = seq_check.SAMPLE


def resolutions(reference, weights, history, config: dict):
    """The reference's (logits, margin given up), best first, under its
    own ``NEAR_TIE`` and ``MAX_STEPS``; of a reference with no near ties
    to resolve, its one answer."""
    if not hasattr(reference, "resolutions"):
        return iter([(reference.last_logits(weights, history, config), 0.0)])
    return reference.resolutions(weights, history, config,
                                 near_tie=reference.NEAR_TIE,
                                 max_steps=reference.MAX_STEPS)


def hold_to_resolutions(found, history, answer, num, reference):
    """``found``: the reference's (logits, margin given up), best
    first. Returns (what is wrong or None, the worst differences, how
    many were tried, the margin given up by the one that agreed or -1):
    of the first resolution the answer agrees with under
    ``seq_check.check_one``'s rules and the reference module's
    ``SCORE_TOL`` and ``RANK_TOL``, else of the one whose scores it
    comes nearest."""
    nearest, tried = None, 0
    for logits, cost in found:
        tried += 1
        why, worst = seq_check.check_one(
            np.asarray(logits, np.float32), history, answer, num,
            reference.SCORE_TOL, reference.RANK_TOL)
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
            resolutions(reference, model.device_tree, histories[u], config),
            histories[u], answer, num, reference)
        worst = {key: max(worst[key], found[key]) for key in worst}
        tried_most, given_up = max(tried_most, tried), max(given_up, cost)
        if why:
            problems.append(f"u{u}: {why} ({tried} resolutions)")
    notes = {"checked": int(len(sample)),
             "score_diff_max": worst["score_diff"],
             "score_tol": reference.SCORE_TOL,
             "rank_gap_max": worst["rank_gap"],
             "rank_tol": reference.RANK_TOL,
             "near_tie": getattr(reference, "NEAR_TIE", 0.0),
             "resolutions_tried_most": int(tried_most),
             "margin_given_up_most": given_up}
    return not problems and len(sample) > 0, problems[:5], notes
