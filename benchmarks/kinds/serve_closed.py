"""Closed loop: the same cell runner as the open loop; the traffic
file's ``kind`` tells the load generator which it is."""

from benchmarks.kinds.serve_open import run  # noqa: F401
