"""The one traffic generator. NumPy only: the load-generator children
import it and must never start JAX.

Every draw comes from ``numpy.random.default_rng([seed, stream])`` so a
seed fixes the ratings, the users who ask and the arrival schedule, and
the host process and its generator children agree without passing
arrays around.
"""

from __future__ import annotations

import numpy as np

# streams of the seed, one per use
RATINGS, QUERY_USERS, ARRIVALS, SAMPLE = 1, 2, 3, 4


def power_law_ids(rng, n: int, size: int, power: float) -> np.ndarray:
    """``size`` ids in [0, n): id = n * r**power, so low ids are heavy
    (``bench.py`` / ``chip_smoke.make_ratings``: power 1.8)."""
    r = rng.random(size, dtype=np.float32)
    np.power(r, np.float32(power), out=r)
    r *= np.float32(n)
    return np.minimum(r.astype(np.int32), np.int32(n - 1))


def make_ratings(config: dict, seed: int):
    """(users, items, vals) of the configuration's rating count: one
    rating for every user and every item first, so both factor tables
    have full width, then the power-law draw."""
    users, items, n = config["users"], config["items"], config["ratings"]
    cover = max(users, items)
    if n < cover:
        raise ValueError(f"ratings {n} cannot cover {users}x{items}")
    rng = np.random.default_rng([seed, RATINGS])
    idx = np.arange(cover, dtype=np.int32)
    u = np.concatenate([idx % users,
                        power_law_ids(rng, users, n - cover, config["power"])])
    i = np.concatenate([idx % items,
                        power_law_ids(rng, items, n - cover, config["power"])])
    vals = rng.integers(1, 11, size=n, dtype=np.int8).astype(np.float32)
    vals *= np.float32(0.5)
    return u, i, vals


def query_pool(config: dict, traffic: dict, seed: int) -> np.ndarray:
    """The users who send queries, in sending order (requests cycle
    through it): drawn by the ratings' power law, so heavy raters ask
    most; ``-1`` marks a user the model never saw."""
    rng = np.random.default_rng([seed, QUERY_USERS])
    size = int(traffic["pool"])
    pool = power_law_ids(rng, config["users"], size,
                         traffic.get("user_power", config["power"]))
    unknown = rng.random(size) < traffic.get("unknown_share", 0.0)
    pool[unknown] = -1
    return pool


def arrivals(traffic: dict, seed: int, stream: int, share: float,
             seconds: float) -> np.ndarray:
    """Due times in [0, seconds) of one generator's share of the open
    loop: exponential gaps (Poisson), optionally on/off bursts."""
    rng = np.random.default_rng([seed, ARRIVALS, stream])
    rate = float(traffic["rate_qps"]) * share
    burst = traffic.get("burst")
    if burst:       # on for `on_s` at rate/duty, off for the rest
        duty = burst["on_s"] / burst["period_s"]
        rate /= duty
    n = int(rate * seconds * 1.2) + 64
    due = np.cumsum(rng.exponential(1.0 / rate, size=n))
    if burst:       # stretch the on-time axis over the on/off periods
        due = (due // burst["on_s"]) * burst["period_s"] + due % burst["on_s"]
    return due[due < seconds]


def request_body(user: int, num: int) -> bytes:
    name = f"u{user}" if user >= 0 else "nobody"
    return b'{"user": "%s", "num": %d}' % (name.encode(), num)
