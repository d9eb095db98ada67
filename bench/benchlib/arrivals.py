"""When each request of a traffic mix is due, from the mix's data file alone.

Every mix (``bench/traffic/<mix>.json``) names its arrival process; this one
generator reads it, so a new mix is a new data file:

* ``"closed"``: one client sends each request when the last one returns.
* ``"periodic"``: a request every ``1 / rate_per_s`` seconds.
* ``"poisson"``: exponential gaps with mean ``1 / rate_per_s``.  Every seed
  gets the same gaps, in its own order, so seeds change the order of the
  work and not its amount.

An optional ``"burst": {"size": n, "every": k}`` makes every k-th arrival
bring n requests at once (all due at the same time).
"""

from __future__ import annotations

import itertools
from collections.abc import Iterator

import numpy as np

BLOCK = 4096          # gaps drawn once and repeated: far more than a window sends
GAP_STREAM = 20250317  # fixed: the same gaps for every seed


def gaps(traffic: dict, seed: int) -> np.ndarray:
    """One block of gaps between arrivals, in seconds."""
    rate = float(traffic["rate_per_s"])
    kind = traffic["arrival"]
    if kind == "periodic":
        return np.full(BLOCK, 1.0 / rate)
    if kind == "poisson":
        drawn = np.random.default_rng(GAP_STREAM).exponential(1.0 / rate, BLOCK)
        order = np.random.default_rng(np.random.SeedSequence([seed, 3]))
        return order.permutation(drawn)
    raise ValueError(f"unknown arrival process {kind!r}")


def due_times(traffic: dict, seed: int) -> Iterator[float | None]:
    """Seconds from the window's start at which each request is due, in
    order; ``None`` for a closed loop, where a request is due when the last
    one returns."""
    if traffic["arrival"] == "closed":
        yield from itertools.repeat(None)
        return
    block = gaps(traffic, seed)
    burst = traffic.get("burst") or {"size": 1, "every": 1}
    t = 0.0
    for i in itertools.count():
        for _ in range(burst["size"] if i % burst["every"] == 0 else 1):
            yield t
        t += float(block[i % BLOCK])
