"""Run one cell of the on-chip benchmark once.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. It names the device and exits 1 without a TPU
(or with fewer chips than the cell asks for); it never falls back to the
CPU. It turns on JAX's persistent compilation cache, builds the cell's
store from the seed under ``<checkout>/.chipbench``, warms the cell's
shapes, runs the traffic for ``--seconds`` and checks the reads it kept.
The last line of standard output is the result as one JSON object; with
``--trace 1`` its metrics are the per-layer ones, read from a profiler
trace of the window and from the store's spans, which only a traced run
turns on.
"""

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [CHECKOUT, os.path.join(CHECKOUT, "src")]
# the TPU runtime's logs stay in the checkout, not under a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR",
                      os.path.join(CHECKOUT, ".chipbench", "tpu_logs"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import harness

    bench = harness.load_json(os.path.join(CHECKOUT, "BENCHMARK.json"))
    wl = next((w for w in bench["workloads"] if w["name"] == args.workload),
              None)
    if wl is None:
        print(f"run: no workload {args.workload!r}", file=sys.stderr)
        return 2

    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"run: {dev.platform} {dev.device_kind} x{len(devices)}",
          file=sys.stderr)
    if dev.platform != "tpu" or len(devices) < int(wl["chips"]):
        print(f"run: needs {wl['chips']} TPU chip(s), found "
              f"{len(devices)} {dev.platform}", file=sys.stderr)
        return 1
    peaks = harness.peaks_for(dev.device_kind)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    # every program goes to the persistent cache, however fast it
    # compiled, so runs after the first compile nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    out = harness.run_cell(bench, args.workload, seed=args.seed,
                           seconds=args.seconds, trace=bool(args.trace),
                           started=STARTED, device=dev, peaks=peaks)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
