"""Open-loop model loads: requests arrive on a fixed schedule, whether or not
earlier ones have finished.

A request is the call a hub's front end makes,
``gateway.load_model(tenant, prefix, template, device=True)``, timed from
its scheduled arrival (not the moment it was sent) until
``block_until_ready`` on the whole tree. The schedule is Poisson at the
mix's ``rate_per_s``, each request a model drawn by Zipf popularity over
``ranks`` and a tenant drawn uniformly, all from the mix's
``schedule_seed``: every run does the same work, whatever its ``--seed``
(which makes the weights). Requests stop at the deadline and the window
ends when the last one lands. A request the gateway sheds
(``RetryAfter``) or that raises is failed.

The check keeps one tree of each group the mix's ``check`` names (a
prefix of model names: ``base``, ``lora``, ``full``), a request of that
group drawn from the run's seed. Requests for different models never share
a flight, so every seed checks a base load and each kind of variant, each
from a load of its own. A group with no request in the window raises.
"""

from __future__ import annotations

import threading
import time
from contextlib import nullcontext
from typing import Any, Callable, ContextManager, List, Set, Tuple

import jax
import numpy as np

from . import Window

# a load still in flight this long past the deadline has failed
GRACE_S = 60.0
# the keys of an open-loop mix
KEYS = ("tenants", "rate_per_s", "zipf_s", "ranks", "schedule_seed",
        "warmup", "check")


def check(mix: dict) -> None:
    """Raise ``ValueError`` where the mix lacks an open-loop key, or checks
    a group that names no model."""
    for key in KEYS:
        if key not in mix:
            raise ValueError(f"open-loop mix: no {key!r}")
    for group in mix["check"]:
        if not any(m.startswith(group) for m in mix["ranks"]):
            raise ValueError(f"open-loop mix: check group {group!r} names "
                             f"no model")


def schedule(mix: dict, seconds: float) -> List[Tuple[float, int, str]]:
    """``(arrival s after the window opens, tenant, model)`` of each
    request that arrives before ``seconds``."""
    rng = np.random.default_rng(int(mix["schedule_seed"]))
    ranks = list(mix["ranks"])
    p = np.arange(1, len(ranks) + 1, dtype=np.float64) ** -float(mix["zipf_s"])
    p /= p.sum()
    out: List[Tuple[float, int, str]] = []
    t = 0.0
    while True:
        t += float(rng.exponential(1.0 / float(mix["rate_per_s"])))
        if t >= seconds:
            return out
        tenant = int(rng.integers(int(mix["tenants"])))
        out.append((t, tenant, ranks[int(rng.choice(len(ranks), p=p))]))


def kept(plan: List[Tuple[float, int, str]], groups: List[str],
         seed: int) -> Set[int]:
    """Indices into ``plan`` of the requests the check keeps: one request
    of each group, drawn from ``seed``."""
    rng = np.random.default_rng([int(seed), 5])
    keep: Set[int] = set()
    for group in groups:
        members = [i for i, (_, _, m) in enumerate(plan)
                   if m.startswith(group)]
        if not members:
            raise ValueError(f"open-loop mix: no {group!r} request in the "
                             f"window to check")
        keep.add(int(rng.choice(members)))
    return keep


def load(built: Any, tenant: int, model: str) -> Any:
    """Send one load; its future."""
    return built.gateway.load_model(f"t{tenant}", built.prefixes[model],
                                    built.template, device=True)


def warm(built: Any, mix: dict, seed: int) -> None:
    """Load each of the mix's ``warmup`` models once, in turn."""
    for model in mix["warmup"]:
        jax.block_until_ready(load(built, 0, model).result())


def run(built: Any, mix: dict, seed: int, seconds: float,
        mark: Callable[[], ContextManager] = nullcontext) -> Window:
    """Send the schedule for ``seconds``; ``mark`` wraps the whole window."""
    w = Window()
    plan = schedule(mix, seconds)
    keep = kept(plan, mix["check"], seed)
    landed: set = set()
    waiters: List[threading.Thread] = []

    def fail(e: BaseException) -> None:
        with w.lock:
            w.failed += 1
            if len(w.errors) < 4:
                w.errors.append(f"{type(e).__name__}: {e}")

    def wait(i: int, due: float, tenant: int, model: str, fut: Any) -> None:
        try:
            tree = jax.block_until_ready(fut.result())
        except Exception as e:  # counted as failed; the window goes on
            fail(e)
            return
        t1 = time.perf_counter()
        with w.lock:
            w.latencies.append(t1 - due)
            w.bytes += sum(int(x.nbytes)
                           for x in jax.tree_util.tree_leaves(tree))
            w.kernel_bytes.append(0)
            w.end = max(w.end, t1)
            landed.add(tenant)
            if i in keep:
                w.kept.append((tenant, model, tree, None))

    with mark():
        w.start = w.end = time.perf_counter()
        for i, (at, tenant, model) in enumerate(plan):
            due = w.start + at
            time.sleep(max(0.0, due - time.perf_counter()))
            with w.lock:
                w.attempted += 1
            try:
                fut = load(built, tenant, model)
            except Exception as e:  # shed (RetryAfter) or refused
                fail(e)
                continue
            t = threading.Thread(target=wait, daemon=True,
                                 args=(i, due, tenant, model, fut))
            t.start()
            waiters.append(t)
        for t in waiters:
            t.join(timeout=max(0.0, w.start + seconds + GRACE_S
                               - time.perf_counter()))
    stuck = sum(t.is_alive() for t in waiters)
    if stuck:  # a load that never comes is a failed load
        with w.lock:
            w.failed += stuck
            w.errors.append(f"{stuck} loads still out {GRACE_S} s after "
                            f"the window closed")
    w.clients_without_reads = len({t for _, t, _ in plan} - landed)
    return w
