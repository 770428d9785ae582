"""Plain references for the ALS recommendation template, in NumPy.

Independent of the code under test: the same tables and ratings give
the same answers.

Serving (``ALSAlgorithm.scala:90-120`` semantics): score = U[u] . V[i]
in float32, seen items masked, the ``num`` best in descending order;
a user the model never saw gets the empty result.

Training (ALS-WR, the lambda scaling ``PARITY.md`` and
``ops/als._normal_eq_solve`` state): row x of the half-step solved last
satisfies (sum_j v_j v_j^T + lambda * n * I) x = sum_j r_j v_j over the
n ratings of that row, checked in float64.
"""

from __future__ import annotations

import numpy as np

#: the program scores with the TPU's default matmul precision: both
#: operands rounded to bfloat16 (8 bits of mantissa, relative error
#: 2^-9 each), products accumulated in float32. So a served score may
#: differ from the float32 reference by up to 2^-8 * sum_k |u_k v_k|,
#: and two items whose reference scores are closer than that may swap.
#: Float32 or better passes with room; int8 or fp8 operands would not.
SCORE_RTOL = 2.0 ** -8


def reference_scores(item_f, user_rows):
    """(users, items) float32: the plain product."""
    return user_rows.astype(np.float32) @ item_f.T


def item_norms(item_f):
    return np.sqrt(np.einsum("ik,ik->i", item_f, item_f))


def check_answer(scores, u_norm: float, item_norm, seen, answer: list,
                 num: int):
    """None when ``answer`` ([(item index, score)], as served) is a
    correct top-``num`` for the user whose reference ``scores`` (one row
    of :func:`reference_scores`) these are, else a sentence saying why
    not. What bfloat16 operands can move item i's score by is bounded
    by ``SCORE_RTOL * |u| * |v_i|`` (Cauchy-Schwarz)."""
    want = min(num, len(scores) - len(np.unique(seen)))
    if len(answer) != want:
        return f"{len(answer)} items, wanted {want}"
    if want == 0:
        return None
    ids = np.array([a[0] for a in answer], dtype=np.int64)
    got = np.array([a[1] for a in answer], dtype=np.float64)

    def tol(ix):
        return SCORE_RTOL * u_norm * item_norm[ix]

    if len(set(ids.tolist())) != len(ids):
        return "an item is repeated"
    if np.isin(ids, seen).any():
        return "a seen item was recommended"
    if np.any(np.diff(got) > 0):
        return "scores are not descending"
    if np.any(np.abs(got - scores[ids]) > tol(ids) + 1e-6):
        return "a score differs from the reference beyond the tolerance"
    # no item left out may beat the weakest item returned by more than
    # the two tolerances: swaps are allowed only between near ties
    floor = float(np.min(scores[ids] + tol(ids)))
    rivals = np.flatnonzero(scores > floor)
    rivals = rivals[~np.isin(rivals, ids) & ~np.isin(rivals, seen)]
    if np.any(scores[rivals] - tol(rivals) > floor):
        return "an item that beats the answer's weakest was left out"
    return None


def normal_equation_residual(x, others, ratings, lam: float) -> float:
    """||A x - b|| / ||b|| in float64 for one solved row."""
    V = others.astype(np.float64)
    r = ratings.astype(np.float64)
    A = V.T @ V + lam * len(r) * np.eye(V.shape[1])
    b = V.T @ r
    return float(np.linalg.norm(A @ x.astype(np.float64) - b)
                 / max(np.linalg.norm(b), 1e-30))
