"""The comparison that decides ``correct``.

Every read the window kept (a sample drawn from the seed, see
``loops/closed.py``) is brought back from HBM and compared bit for bit with
the plain numpy reference made again from the seed. The store promises the
bytes it was given, so every number here has the limit 0:

* ``mismatched_elements``: elements whose bits differ from the reference
  (a result of the wrong shape or dtype counts every element);
* ``failed_reads``: reads that raised or never came;
* ``off_device_reads``: kept reads whose result (every leaf, where it is
  a tree of arrays) is not a jax array on the chip the cell runs on;
* ``clients_without_reads``: clients that finished no read in the window.

With ``control=True`` each kept result is replaced by the reference in the
nearest precision below the configuration's (``Built.control``); that run
has to come out not correct.
"""

from __future__ import annotations

from typing import Any, Dict, List

import jax
import numpy as np

from .kinds import Built
from .loops import Window

LIMITS = {"mismatched_elements": 0, "failed_reads": 0, "off_device_reads": 0,
          "clients_without_reads": 0}


def mismatched(got: np.ndarray, want: np.ndarray) -> int:
    """Elements of ``got`` whose bits differ from ``want``."""
    if got.shape != want.shape or got.dtype != want.dtype:
        return int(want.size)
    a = np.ascontiguousarray(got).view(np.uint8).reshape(got.size, -1)
    b = np.ascontiguousarray(want).view(np.uint8).reshape(want.size, -1)
    return int(np.count_nonzero((a != b).any(axis=1)))


def mismatched_tree(got: Any, want: Any) -> int:
    """:func:`mismatched` summed over the leaves of two trees of arrays (one
    array is a tree of one leaf); trees of another structure differ in
    every element."""
    g, g_def = jax.tree_util.tree_flatten(got)
    w, w_def = jax.tree_util.tree_flatten(want)
    if g_def != w_def:
        return sum(int(np.size(x)) for x in w)
    return sum(mismatched(np.asarray(a), np.asarray(b)) for a, b in zip(g, w))


def host_copies(window: Window, device: Any) -> List[Dict[str, Any]]:
    """Bring the kept reads (arrays or trees of them) to the host and drop
    their device buffers."""
    out = []
    for client, spec, result, info in window.kept:
        leaves = jax.tree_util.tree_leaves(result)
        on_device = bool(leaves) and all(
            isinstance(x, jax.Array) and x.devices() == {device}
            for x in leaves)
        out.append({"client": client, "spec": spec, "on_device": on_device,
                    "host": jax.tree_util.tree_map(np.asarray, result)})
    window.kept.clear()
    return out


def compare(built: Built, window: Window, kept: List[Dict[str, Any]], *,
            control: bool = False) -> Dict[str, Dict[str, int]]:
    """Each number compared, with its limit."""
    bad = off = 0
    for k in kept:
        want = built.reference(k["spec"])
        got = built.control(want) if control else k["host"]
        bad += mismatched_tree(got, want)
        off += 0 if k["on_device"] else 1
    values = {"mismatched_elements": bad, "failed_reads": window.failed,
              "off_device_reads": off,
              "clients_without_reads": window.clients_without_reads}
    return {name: {"value": v, "limit": LIMITS[name]}
            for name, v in values.items()}


def passed(numbers: Dict[str, Dict[str, int]]) -> bool:
    """Every number within its limit."""
    return all(n["value"] <= n["limit"] for n in numbers.values())
