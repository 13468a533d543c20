"""Window loops, one module per mix ``loop``; the harness finds each by
name (``chipbench.loops.<loop>``).

A loop module defines

* ``check(mix)``: raise ``ValueError`` where the mix lacks a key the loop
  needs (each loop defines its own keys; ``closed`` needs a ``slice``
  deck);
* ``warm(built, mix, seed)``: every shape the window will use, before it;
* ``run(built, mix, seed, seconds, mark)``: the window, wrapped in
  ``mark()``, returned as a :class:`Window`.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Any, List, Optional, Tuple

from ..kinds import Spec


@dataclass
class Window:
    """What the window did, for the metrics and the check."""

    start: float = 0.0
    end: float = 0.0
    latencies: List[float] = field(default_factory=list)
    bytes: int = 0
    attempted: int = 0
    failed: int = 0
    errors: List[str] = field(default_factory=list)
    kernel_bytes: List[Optional[int]] = field(default_factory=list)
    # (client, spec, result, info) of the reads the check keeps; a result
    # is an array or a tree of arrays
    kept: List[Tuple[int, Spec, Any, Any]] = field(default_factory=list)
    clients_without_reads: int = 0
    lock: threading.Lock = field(default_factory=threading.Lock, repr=False)

    @property
    def seconds(self) -> float:
        """Length of the window."""
        return self.end - self.start

    @property
    def reads(self) -> int:
        """Reads completed."""
        return len(self.latencies)
