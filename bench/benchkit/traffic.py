"""The one traffic generator: every mix is a data file it reads.

Two loops:

* ``open``: independent clients.  ``round(rate_hz * seconds)`` requests
  arrive on a Poisson schedule; each is timed from its scheduled arrival,
  so a driver that falls behind still charges the system.  Every seed
  gets the same set of gaps (the exponential distribution's quantiles at
  ``(i + 0.5) / N``) and the same multiset of widths (``round(N * p)`` of
  each), each in its own seeded order: seeds change which request comes
  when, not how much work a window holds.
* ``closed``: one client sending its next job when the last one returned.

Right-hand sides are standard normals from ``(seed, index)``.
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np


def seed_key(seed: int) -> int:
    """Any whole number (negative or past 2**63) as an RNG seed."""
    return int(seed) % (1 << 64)


@dataclasses.dataclass(frozen=True)
class Arrival:
    t: float        # seconds after the window opens
    width: int      # right-hand-side columns


def open_schedule(mix: dict, seed: int, seconds: float) -> List[Arrival]:
    """The open-loop schedule of ``mix`` for a window of ``seconds``."""
    n = max(1, int(round(float(mix["rate_hz"]) * seconds)))
    rng = np.random.default_rng([seed_key(seed), 1])
    widths = []
    shares = mix["widths"]          # [[width, share], ...]
    for width, share in shares[1:]:
        widths += [int(width)] * int(round(n * float(share)))
    widths = [int(shares[0][0])] * (n - len(widths)) + widths
    widths = rng.permutation(np.asarray(widths[:n], np.int64))
    q = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-q) / float(mix["rate_hz"]))
    t = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return [Arrival(t=float(t[i]), width=int(widths[i])) for i in range(n)]


def rhs(seed: int, index: int, n: int, width: int,
        warmup: bool = False) -> np.ndarray:
    """Request ``index``'s right-hand side: ``[n]`` for one column, else
    ``[n, width]``, ``float32``.  Set-up's requests draw from a stream of
    their own."""
    rng = np.random.default_rng([seed_key(seed), 3 if warmup else 2, index])
    b = rng.standard_normal((n, width), dtype=np.float32)
    return b[:, 0] if width == 1 else b


def max_group_columns(mix: dict) -> int:
    """Widest group the daemon can form: it fills up to
    ``max_batch_columns`` and may overshoot by one request."""
    widest = max(int(w) for w, _ in mix["widths"])
    return int(mix["max_batch_columns"]) + widest - 1


def buckets(columns: int) -> List[int]:
    """Every power-of-two solve width up to the one ``columns`` pads to."""
    out = [1]
    while out[-1] < columns:
        out.append(out[-1] * 2)
    return out
