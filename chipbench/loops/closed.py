"""Closed-loop read clients: each sends its next read when the last lands.

A read is the call users make, ``store.open(tid).read_device(spec)``, timed
from the moment it is sent until ``block_until_ready`` on its result. Clients stop
issuing at the deadline and finish the read they hold; the window ends when
the last of those lands, so its rate counts all the work and all the time.
"""

from __future__ import annotations

import threading
import time
from contextlib import nullcontext
from typing import Any, Callable, ContextManager, List, Tuple

import jax
import numpy as np

from .. import loadgen
from ..kinds import Built, Spec
from . import Window

# a read still in flight this long past the deadline has failed
GRACE_S = 60.0
# the keys of a closed-loop mix (see loadgen)
KEYS = ("clients", "slice", "warmup", "check_per_client")


def check(mix: dict) -> None:
    """Raise ``ValueError`` where the mix lacks a closed-loop key."""
    for key in KEYS:
        if key not in mix:
            raise ValueError(f"closed-loop mix: no {key!r}")


def read_once(built: Built, spec: Spec) -> Tuple[Any, Any]:
    """One read into HBM, waited for."""
    with jax.profiler.TraceAnnotation("chipbench.read"):
        with built.store.open(built.tensor_id) as ref:
            out, info = ref.read_device(list(spec), with_info=True)
        wait = getattr(out, "block_until_ready", None)
        if wait is not None:
            wait()
    return out, info


def warm(built: Built, mix: dict, seed: int) -> None:
    """Each client's warm-up reads, clients side by side."""
    errors: List[BaseException] = []

    def client(specs: List[Spec]) -> None:
        try:
            for spec in specs:
                read_once(built, spec)
        except BaseException as e:  # re-raised on the main thread
            errors.append(e)

    threads = [threading.Thread(target=client, args=(s,), daemon=True)
               for s in loadgen.warmup(mix, built.shape, seed)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]


def run(built: Built, mix: dict, seed: int, seconds: float,
        mark: Callable[[], ContextManager] = nullcontext) -> Window:
    """Drive the mix for ``seconds``; ``mark`` wraps the whole window."""
    w = Window()
    n = int(mix["clients"])
    keep = int(mix["check_per_client"])
    go = threading.Event()

    def client(c: int) -> None:
        reqs = loadgen.stream(mix, built.shape, seed, c)
        pick = np.random.default_rng([int(seed), 4, c])
        kept: List[Tuple[int, Spec, Any, Any]] = []
        done = 0
        go.wait()
        deadline = w.start + seconds
        for spec in reqs:
            t0 = time.perf_counter()
            if t0 >= deadline:
                break
            with w.lock:
                w.attempted += 1
            try:
                out, info = read_once(built, spec)
            except Exception as e:  # counted as failed; the window goes on
                with w.lock:
                    w.failed += 1
                    if len(w.errors) < 4:
                        w.errors.append(f"{type(e).__name__}: {e}")
                continue
            t1 = time.perf_counter()
            with w.lock:
                w.latencies.append(t1 - t0)
                w.bytes += int(out.nbytes)
                w.kernel_bytes.append(built.kernel_bytes(spec))
                w.end = max(w.end, t1)
            # reservoir sample of this client's reads, drawn from the seed
            slot = done if done < keep else int(pick.integers(0, done + 1))
            if slot < keep:
                item = (c, spec, out, info)
                if slot < len(kept):
                    kept[slot] = item
                else:
                    kept.append(item)
            done += 1
        with w.lock:
            w.kept.extend(kept)
            if not done:
                w.clients_without_reads += 1

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(n)]
    for t in threads:
        t.start()
    with mark():
        w.start = w.end = time.perf_counter()
        go.set()
        for t in threads:
            t.join(timeout=max(0.0, w.start + seconds + GRACE_S
                               - time.perf_counter()))
    stuck = sum(t.is_alive() for t in threads)
    if stuck:  # a read that never comes is a failed read
        with w.lock:
            w.failed += stuck
            w.errors.append(f"{stuck} reads still out {GRACE_S} s after "
                            f"the window closed")
    return w
