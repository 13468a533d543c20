"""Serving launcher: continuous-batching engine over a checkpoint.

    PYTHONPATH=src python -m repro.launch.serve --arch granite-3-8b \
        --reduced --requests 8 --slots 4 [--ckpt-dir /tmp/repro_ckpts]

Loads params from the latest delta-lake checkpoint when one exists
(elastic: any mesh/host count can restore), else serves fresh-initialized
weights (layout/perf testing). With ``--weights-dir`` the params come
from a serve-weights store instead, through the snapshot-pinned
``store.models(prefix)`` handle (one merged cold-start fetch plan, each
leaf landing in device memory); the engine owns that handle and releases
its lease on close. Restored checkpoint params are placed on the device
once, before the engine is built.

The default ``granite-3-8b`` takes about 16 GB in bf16, all of one TPU
v5e chip's HBM, so it does not fit one chip; ``phi3-mini-3.8b`` (about
7.6 GB) does.
"""

from __future__ import annotations

import argparse
import time
from typing import Any, List, Optional, Tuple

import jax
import numpy as np

from ..lake import LocalFSObjectStore
from ..models import transformer
from ..models.config import get_arch
from ..serve import Request, ServeEngine
from ..train import checkpoint as ckpt_mod, trainer
from .compile_cache import enable_compile_cache

PROMPT_LEN = 16  # one prompt length, so prefill compiles once


def main(argv: Optional[List[str]] = None) -> Tuple[Any, List[Request]]:
    """Serve ``--requests`` requests; returns the device params the engine
    served with and the finished requests."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-8b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-shards", type=int, default=None,
                    help="shard count for the checkpoint store (fixed at "
                         "store-create time; omit to use what exists)")
    ap.add_argument("--ckpt-gc-keep", type=int, default=None,
                    help="after the restore completes, prune checkpoints "
                         "beyond the newest N and vacuum the reclaimed "
                         "bytes")
    ap.add_argument("--weights-dir", default=None,
                    help="serve-weights store directory; loads params via "
                         "store.models(--weights-prefix) instead of a "
                         "checkpoint")
    ap.add_argument("--weights-prefix", default="serve_weights",
                    help="model prefix inside --weights-dir")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if not cfg.supports_decode:
        raise SystemExit(f"{cfg.name}: no decode step")

    key = jax.random.key(args.seed)
    init = jax.jit(transformer.init_params, static_argnums=0)
    params = None
    repo = None
    if args.weights_dir:
        from ..core import DeltaTensorStore
        wstore = DeltaTensorStore(LocalFSObjectStore(args.weights_dir),
                                  "weights")
        repo = wstore.models(args.weights_prefix)
        if repo.exists():
            params = repo.load(jax.eval_shape(lambda k: init(cfg, k), key),
                               device=True)
            print(f"[serve] loaded {repo.stats()['leaves']} param leaves "
                  f"from {args.weights_dir!r} prefix "
                  f"{args.weights_prefix!r} @ v{repo.version}")
        else:
            params = init(cfg, key)
            repo.save(params)
            print(f"[serve] seeded fresh weights into {args.weights_dir!r} "
                  f"prefix {args.weights_prefix!r}")
    elif args.ckpt_dir:
        ckpt = ckpt_mod.DeltaCheckpointer(LocalFSObjectStore(args.ckpt_dir),
                                          shards=args.ckpt_shards)
        if ckpt.restore_available():
            step, state = ckpt.restore(trainer.init_state(cfg, key))
            # one host-to-device copy; a host tree would be copied again
            # by every call of the jitted prefill and decode
            params = jax.device_put(state.params)
            print(f"[serve] restored params from checkpoint step {step}")
            if args.ckpt_gc_keep is not None:
                gc = ckpt.gc(keep=args.ckpt_gc_keep)
                print(f"[serve] checkpoint gc: pruned steps "
                      f"{gc['pruned_steps']}, reclaimed "
                      f"{gc['bytes_reclaimed']} bytes "
                      f"({gc['files_deleted']} files)")
    if params is None:
        params = init(cfg, key)

    extra = {}
    if cfg.family == "vlm":
        extra["image_embeds"] = jax.numpy.zeros(
            (args.slots, cfg.n_image_tokens, cfg.d_model), jax.numpy.float32)
    with ServeEngine(params, cfg, n_slots=args.slots, max_len=args.max_len,
                     extra_inputs=extra, repo=repo) as eng:
        rng = np.random.default_rng(args.seed)
        reqs = [Request(rid=i,
                        prompt=rng.integers(0, cfg.vocab_size,
                                            (PROMPT_LEN,)).astype(np.int32),
                        max_new_tokens=args.max_new)
                for i in range(args.requests)]
        for r in reqs:
            eng.submit(r)
        t0 = time.time()
        eng.run_until_drained()
        dt = time.time() - t0
        tok = sum(len(r.out_tokens) for r in reqs)
        print(f"[serve] {len(reqs)} requests, {tok} tokens, {dt:.2f}s "
              f"({tok/dt:.1f} tok/s) on {args.slots} slots")
    return params, reqs


if __name__ == "__main__":
    main()
