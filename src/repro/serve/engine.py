"""Serving engine: batched prefill + decode with continuous slot batching.

Slots are fixed (static shapes for jit); finished sequences free their slot
and the engine immediately prefill-admits the next queued request into it.
Per-slot KV caches live in one batched cache pytree, so decode is a single
jit'd step for the whole batch regardless of request boundaries.

Weights live in the Delta Tensor store as one FTSF tensor per param leaf,
managed through :class:`~repro.serve.repo.ModelRepo`
(``store.models(prefix)``): a snapshot-pinned, lease-holding handle whose
``load`` pulls the whole tree through one merged ``Catalog.read_many``
fetch plan on the shared :class:`~repro.lake.io.ReadExecutor` —
deduplicated keys, windowed submission, per-leaf decode overlapping
in-flight fetches — so cold-start weight load time is the makespan of
parallel object-store gets, not the serial sum. The old free functions
:func:`save_weights` / :func:`load_weights` survive as deprecated shims
over that handle. Multi-tenant admission control lives one layer up in
:class:`~repro.serve.gateway.Gateway`.
"""

from __future__ import annotations

import queue
import warnings
import weakref
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.store import DeltaTensorStore
from ..lake.io import ReadExecutor
from ..models import transformer
from ..models.config import ArchConfig
from .repo import ModelRepo


# -- weight load/store (deprecated shims over ModelRepo) ----------------------


def save_weights(store: DeltaTensorStore, params: Any, *,
                 prefix: str = "serve_weights") -> List[str]:
    """Deprecated: use ``store.models(prefix).save(params)``.

    Thin shim over :meth:`repro.serve.repo.ModelRepo.save` — identical
    behavior (one FTSF tensor per leaf, ONE atomic commit, re-save
    replaces the previous generation), kept for existing callers.
    """
    warnings.warn(
        "save_weights is deprecated; use store.models(prefix).save(params)",
        DeprecationWarning, stacklevel=2)
    with store.models(prefix) as repo:
        return repo.save(params)


def load_weights(store: DeltaTensorStore, template: Any, *,
                 prefix: str = "serve_weights",
                 io: Optional[ReadExecutor] = None) -> Any:
    """Deprecated: use ``store.models(prefix).load(template)``.

    Thin shim over :meth:`repro.serve.repo.ModelRepo.load` — identical
    behavior (whole tree through ONE merged fetch plan against one pinned
    catalog). ``io=`` now actually threads through to ``read_many``
    (historically it was accepted and silently ignored).
    """
    warnings.warn(
        "load_weights is deprecated; use store.models(prefix).load(template)",
        DeprecationWarning, stacklevel=2)
    with store.models(prefix) as repo:
        return repo.load(template, io=io)


@dataclass
class Request:
    rid: int
    prompt: np.ndarray            # (T,) int32
    max_new_tokens: int = 16
    eos_id: Optional[int] = None
    out_tokens: List[int] = field(default_factory=list)
    done: bool = False


class ServeEngine:
    """Continuous-batching inference engine over store-resident weights.

    ``close()`` / context-manager exit / garbage collection release the
    engine's resources — in particular the snapshot lease of a weight
    repo passed as ``repo=`` (or via :meth:`from_repo`), which the engine
    then owns. Same lifecycle contract as ``TensorRef``, ``StreamLoader``,
    ``ModelRepo``, and ``Gateway``.
    """

    def __init__(self, params, cfg: ArchConfig, *, n_slots: int, max_len: int,
                 extra_inputs: Optional[Dict[str, Any]] = None,
                 enc_len: int = 1, repo: Optional[ModelRepo] = None):
        self.params = params
        self.cfg = cfg
        self.n_slots = n_slots
        self.max_len = max_len
        self.enc_len = enc_len
        self.extra = extra_inputs or {}
        self.caches = transformer.init_caches(cfg, n_slots, max_len,
                                              enc_len=enc_len)
        self.slot_req: List[Optional[Request]] = [None] * n_slots
        self.slot_len = np.zeros(n_slots, np.int32)
        self.queue: "queue.Queue[Request]" = queue.Queue()
        self._repo = repo
        # GC backstop: a dropped engine must not pin its weight snapshot
        self._finalizer = (weakref.finalize(self, repo.close)
                           if repo is not None
                           else weakref.finalize(self, lambda: None))

        self._decode = jax.jit(
            lambda params, tok, caches, extra: transformer.decode_step(
                params, cfg, tok, caches, **extra))
        # prefill one slot at a time (batch=1 lane), written into the slot
        self._prefill = jax.jit(
            lambda params, tok, caches, extra: transformer.prefill(
                params, cfg, tok, caches, **extra),
            static_argnames=())

    # -- lifecycle -------------------------------------------------------------

    @classmethod
    def from_repo(cls, repo: ModelRepo, template: Any, cfg: ArchConfig, *,
                  n_slots: int, max_len: int, **kwargs) -> "ServeEngine":
        """Build an engine whose weights load from ``repo`` into device
        memory (one merged fetch plan); the engine owns the handle and
        releases its snapshot lease on ``close()``."""
        params = repo.load(template, device=True)
        return cls(params, cfg, n_slots=n_slots, max_len=max_len,
                   repo=repo, **kwargs)

    def close(self) -> None:
        """Release engine resources (idempotent): drop queued and in-slot
        requests and release the owned weight repo's snapshot lease."""
        self.slot_req = [None] * self.n_slots
        self.slot_len[:] = 0
        while not self.queue.empty():
            try:
                self.queue.get_nowait()
            except queue.Empty:  # pragma: no cover - racing drain
                break
        self._finalizer()

    @property
    def closed(self) -> bool:
        """Whether :meth:`close` has run (weight snapshot lease released)."""
        return not self._finalizer.alive

    def __enter__(self) -> "ServeEngine":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()

    # -- slot management -----------------------------------------------------

    def submit(self, req: Request) -> None:
        if self.closed:
            raise RuntimeError("engine is closed")
        self.queue.put(req)

    def _admit(self) -> None:
        for s in range(self.n_slots):
            if self.slot_req[s] is not None or self.queue.empty():
                continue
            req = self.queue.get()
            t = len(req.prompt)
            # single-request prefill on a 1-lane cache, then splice into slot s
            lane = transformer.init_caches(self.cfg, 1, self.max_len,
                                           enc_len=self.enc_len)
            tok = jnp.asarray(req.prompt[None], jnp.int32)
            extra = {k: v[:1] for k, v in self.extra.items()}
            logits, lane, _ = self._prefill(self.params, tok, lane, extra)
            first = int(jnp.argmax(logits[0, -1]))
            req.out_tokens.append(first)

            def splice(full, one):
                # the batch axis is wherever the 1-lane shape differs
                # (layer-stacked cache leaves carry leading scan dims)
                if full.shape == one.shape:
                    return one if self.n_slots == 1 else full
                axis = next(d for d in range(full.ndim)
                            if full.shape[d] != one.shape[d])
                start = [0] * full.ndim
                start[axis] = s
                return jax.lax.dynamic_update_slice(full,
                                                    one.astype(full.dtype),
                                                    start)

            self.caches = jax.tree.map(splice, self.caches, lane)
            self.slot_req[s] = req
            # cache holds t entries; the pending token writes at index t
            self.slot_len[s] = t

    # -- decode loop -----------------------------------------------------------

    def _sync_index(self) -> None:
        # per-slot cache indices (ragged lengths under continuous batching)
        self.caches = dict(self.caches)
        self.caches["index"] = jnp.asarray(self.slot_len, jnp.int32)

    def step(self) -> int:
        """One engine iteration: admit, decode all active slots, retire."""
        self._admit()
        active = [s for s in range(self.n_slots) if self.slot_req[s] is not None]
        if not active:
            return 0
        self._sync_index()
        tok = np.zeros((self.n_slots, 1), np.int32)
        for s in active:
            tok[s, 0] = self.slot_req[s].out_tokens[-1]
        logits, self.caches, _ = self._decode(
            self.params, jnp.asarray(tok), self.caches,
            {k: v[: self.n_slots] for k, v in self.extra.items()})
        nxt = np.asarray(jnp.argmax(logits[:, 0], axis=-1), np.int32)
        for s in active:
            req = self.slot_req[s]
            req.out_tokens.append(int(nxt[s]))
            self.slot_len[s] += 1
            hit_eos = req.eos_id is not None and int(nxt[s]) == req.eos_id
            if (len(req.out_tokens) >= req.max_new_tokens or hit_eos
                    or self.slot_len[s] >= self.max_len - 1):
                req.done = True
                self.slot_req[s] = None
                self.slot_len[s] = 0
        return len(active)

    def run_until_drained(self, max_iters: int = 10_000) -> None:
        for _ in range(max_iters):
            if self.step() == 0 and self.queue.empty():
                return
        raise RuntimeError("serve loop did not drain")
