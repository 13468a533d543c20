"""The closed loop's traffic generator: slice requests from a mix's
parameter file.

A closed-loop mix (``chipbench/traffic/<name>.json``, checked by
``loops.closed.check``) gives

* ``clients``: closed-loop clients, each waiting for its read before the
  next;
* ``slice``: one entry per leading dimension, ``{"start": [lo, hi],
  "length": n}``: each request reads ``[s, s + n)`` with ``s`` in
  ``lo..hi``;
* ``warmup``: ``"each_start"`` reads every start once before the window
  (where starts change the compiled shapes), or a number of reads per
  client;
* ``check_per_client``: how many of each client's reads the check keeps.

Every client walks a deck of all starts, shuffled from ``(seed, client)``
and shuffled again each time round, so every seed reads the same set of
slices in another order.
"""

from __future__ import annotations

import itertools
from typing import Any, Dict, Iterator, List, Sequence

import numpy as np

from .kinds import Spec


def starts(mix: Dict[str, Any], shape: Sequence[int]) -> List[Spec]:
    """Every request the mix can make, in a fixed order."""
    per_dim = []
    for d, dim in enumerate(mix["slice"]):
        lo, hi = (int(v) for v in dim["start"])
        n = int(dim["length"])
        if not (0 <= lo <= hi and hi + n <= int(shape[d]) and n > 0):
            raise ValueError(f"slice {dim} does not fit dimension {d} of "
                             f"{tuple(shape)}")
        per_dim.append([(s, s + n) for s in range(lo, hi + 1)])
    return [tuple(c) for c in itertools.product(*per_dim)]


def stream(mix: Dict[str, Any], shape: Sequence[int], seed: int,
           client: int) -> Iterator[Spec]:
    """Client ``client``'s endless requests for the window."""
    deck = starts(mix, shape)
    rng = np.random.default_rng([int(seed), 2, int(client)])
    while True:
        for i in rng.permutation(len(deck)):
            yield deck[i]


def warmup(mix: Dict[str, Any], shape: Sequence[int], seed: int
           ) -> List[List[Spec]]:
    """Each client's requests before the window."""
    n = int(mix["clients"])
    if mix["warmup"] == "each_start":
        deck = starts(mix, shape)
        return [deck[c::n] for c in range(n)]
    k = int(mix["warmup"])
    out = []
    for c in range(n):
        rng = np.random.default_rng([int(seed), 3, c])
        deck = starts(mix, shape)
        out.append([deck[i] for i in rng.integers(0, len(deck), k)])
    return out
