"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch granite-3-8b \
        --reduced --steps 100 --ckpt-dir /tmp/ckpts

On a real TPU fleet this same entry point runs under `jax.distributed`
(one process per host): the mesh comes from `launch.mesh`, the data
pipeline shards by host, checkpoints commit atomically through the delta
log, and a restart resumes from the last committed step. On this CPU box
use ``--reduced`` (the smoke-twin config) — full configs are exercised via
``repro.launch.dryrun``.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..core.store import DeltaTensorStore
from ..data.pipeline import FTSFLoader, write_token_dataset
from ..data.synthetic import token_stream
from ..lake import LocalFSObjectStore
from ..models.config import get_arch
from ..train import checkpoint as ckpt_mod, optimizer as opt, trainer
from .compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-8b")
    ap.add_argument("--reduced", action="store_true",
                    help="use the CPU smoke-twin config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpts")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--data-dir", default="/tmp/repro_data")
    ap.add_argument("--host-index", type=int, default=0)
    ap.add_argument("--n-hosts", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    print(f"[train] arch={cfg.name} layers={cfg.n_layers} "
          f"d_model={cfg.d_model} devices={jax.device_count()}")

    # --- data: FTSF rows in a delta table on local disk --------------------
    data_store = DeltaTensorStore(LocalFSObjectStore(args.data_dir), "datasets")
    try:
        data_store.shape_of("corpus")
    except KeyError:
        tokens = token_stream(max(1024, 8 * args.batch), args.seq,
                              cfg.vocab_size, seed=args.seed)
        write_token_dataset(data_store, tokens, tensor_id="corpus")
    loader = FTSFLoader(data_store, "corpus", batch_size=args.batch,
                        host_index=args.host_index, n_hosts=args.n_hosts,
                        seed=args.seed)

    # --- state: fresh or restored from the last committed checkpoint -------
    ocfg = opt.OptConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 1),
                         total_steps=args.steps)
    ckpt = ckpt_mod.DeltaCheckpointer(LocalFSObjectStore(args.ckpt_dir))
    state = trainer.init_state(cfg, jax.random.key(args.seed))
    start = 0
    if ckpt.restore_available():
        start, state = ckpt.restore(state)
        print(f"[train] resumed from committed step {start}")
    step_fn = jax.jit(trainer.make_train_step(cfg, ocfg))

    it = iter(loader)
    t0 = time.time()
    for i in range(start, args.steps):
        b = next(it)
        state, m = step_fn(state, {"tokens": jnp.asarray(b["tokens"]),
                                   "labels": jnp.asarray(b["labels"])})
        if (i + 1) % args.ckpt_every == 0 or i + 1 == args.steps:
            ckpt.save_async(i + 1, state)
        if (i + 1) % 10 == 0:
            print(f"[train] step {i+1:5d} loss {float(m['loss']):.4f} "
                  f"lr {float(m['lr']):.2e} "
                  f"({(i+1-start)/(time.time()-t0):.2f} steps/s)")
    ckpt.wait()
    loader.close()
    print(f"[train] done; checkpoints at steps {ckpt.steps()}")


if __name__ == "__main__":
    main()
