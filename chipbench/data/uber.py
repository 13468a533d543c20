"""Uber-pickups-like 4-D COO tensor (day, hour, lat, lon) from the seed.

The spatial and hourly structure follows the program's
``repro.data.synthetic.uber_like`` (copied here so that a change to the
program cannot change the data the benchmark measures): a hot core of about
0.15% of the grid gathered around six hubs, Zipf-like popularity over those
cells, and pickup hours around 18:00. Three things differ from it:

* the non-zeros are **distinct**: candidates are drawn with that structure
  and collisions are merged, and each day keeps the first ``K_d`` distinct
  cells it drew, so the tensor has exactly the configured count (the
  program's generator sums collisions and ends with about half as many);
* ``K_d``, the non-zeros of day ``d``, does not depend on the seed: it
  follows a fixed weekly profile that sums to the configured count. Every
  seed then reads slices of the same sizes, so the programs compiled for
  one seed serve every other; the seed moves only which cells and hours;
* the hubs spread wider (the configuration's ``hub_sigma``): at the
  program's spread of 3 cells the core holds about 800 distinct cells,
  and 24 hours of those cannot hold a day's share of distinct non-zeros.

Values are each cell's share of its day's pickups (the merged draw counts
over the day's total) in float32, so their mantissas are full.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence, Tuple

import numpy as np


def day_counts(n_days: int, nnz: int, weekly: Sequence[float]) -> np.ndarray:
    """Non-zeros per day: the weekly profile, scaled to sum to ``nnz``."""
    w = np.asarray([weekly[d % len(weekly)] for d in range(n_days)], float)
    exact = w / w.sum() * nnz
    counts = np.floor(exact).astype(np.int64)
    # hand the remainder to the days with the largest fractional parts
    short = int(nnz - counts.sum())
    order = np.argsort(-(exact - counts), kind="stable")
    counts[order[:short]] += 1
    return counts


def _hot_cells(rng: np.random.Generator, la: int, lo: int,
               p: Dict[str, Any]) -> np.ndarray:
    n_cells = max(32, int(la * lo * p["hot_cell_share"]))
    n_hubs = int(p["hubs"])
    hubs = np.stack([rng.integers(la // 8, la - la // 8, n_hubs),
                     rng.integers(lo // 8, lo - lo // 8, n_hubs)], axis=1)
    hub_of = rng.integers(0, n_hubs, n_cells)
    sigma = float(p["hub_sigma"])
    cells = np.stack([
        np.clip(hubs[hub_of, 0] + rng.normal(0, sigma, n_cells).astype(int),
                0, la - 1),
        np.clip(hubs[hub_of, 1] + rng.normal(0, sigma, n_cells).astype(int),
                0, lo - 1)], axis=1)
    return np.unique(cells, axis=0)


def generate(seed: int, shape: Sequence[int], nnz: int,
             p: Dict[str, Any]) -> Tuple[np.ndarray, np.ndarray]:
    """``(indices (nnz, 4) int64, values (nnz,) float32)``, day-major.

    Within a day the entries are in the order they were first drawn.
    """
    shape = tuple(int(s) for s in shape)
    n_days, n_hours, la, lo = shape
    rng = np.random.default_rng([int(seed), 1])
    cells = _hot_cells(rng, la, lo, p)
    pop = 1.0 / np.arange(1, len(cells) + 1) ** float(p["zipf"])
    pop /= pop.sum()
    want = day_counts(n_days, nnz, p["weekly"])
    per_day = n_hours * la * lo
    keys = np.zeros(0, np.int64)
    for _ in range(8):
        # draw twice each day's count per round, until every day has
        # enough distinct cells
        day = np.repeat(np.arange(n_days), 2 * want)
        m = len(day)
        which = rng.choice(len(cells), size=m, p=pop)
        hour = rng.normal(p["hour_mean"], p["hour_sigma"], m).astype(int)
        hour %= n_hours
        keys = np.concatenate([keys, np.ravel_multi_index(
            (day, hour, cells[which, 0], cells[which, 1]), shape)])
        uniq, first, mult = np.unique(keys, return_index=True,
                                      return_counts=True)
        have = np.bincount(uniq // per_day, minlength=n_days)
        if (have >= want).all():
            break
    else:
        raise ValueError(f"cannot draw {nnz} distinct non-zeros in {shape}")
    # first K_d distinct keys of each day, in draw order
    order = np.argsort(first, kind="stable")
    uniq, mult = uniq[order], mult[order]
    day_of = uniq // per_day
    by_day = np.argsort(day_of, kind="stable")
    uniq, mult, day_of = uniq[by_day], mult[by_day], day_of[by_day]
    start = np.concatenate([[0], np.cumsum(have)[:-1]])
    rank = np.arange(len(uniq)) - start[day_of]
    keep = rank < want[day_of]
    uniq, mult, day_of = uniq[keep], mult[keep], day_of[keep]
    total = np.bincount(day_of, weights=mult, minlength=n_days)
    values = (mult / total[day_of]).astype(np.float32)
    idx = np.stack(np.unravel_index(uniq, shape), axis=1).astype(np.int64)
    return idx, values


def dense_slice(indices: np.ndarray, values: np.ndarray,
                shape: Sequence[int], spec: Sequence[Tuple[int, int]]
                ) -> np.ndarray:
    """The dense array ``X[spec]`` of a COO tensor, by a mask and a scatter.

    ``spec`` gives ``(lo, hi)`` for the leading dimensions; the rest are
    taken whole.
    """
    full = [tuple(s) for s in spec] + [(0, int(d)) for d in shape[len(spec):]]
    keep = np.ones(len(values), bool)
    for d, (lo, hi) in enumerate(full):
        keep &= (indices[:, d] >= lo) & (indices[:, d] < hi)
    out = np.zeros([hi - lo for lo, hi in full], values.dtype)
    at = indices[keep] - np.asarray([lo for lo, _ in full], np.int64)
    out[tuple(at.T)] = values[keep]
    return out
