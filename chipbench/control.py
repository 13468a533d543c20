"""Run a cell's control on the chip: the reference in the nearest precision
below the configuration's, compared in the program's place.

    python3 chipbench/control.py --workload <name> --seeds 1,2,3 --seconds 5

One process, one short window per seed at the cell's own size; each prints
a JSON line with the control's numbers (``checks``, which must fail) and
the program's on the same reads (``program_checks``). The benchmark's own
runs never run this.
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)

    import jax

    from chipbench import harness
    from repro.launch.compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"control: needs a TPU, found {dev.platform}", file=sys.stderr)
        return 1
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    bench = harness.load_json(os.path.join(CHECKOUT, "BENCHMARK.json"))
    failed_all = True
    for seed in (int(s) for s in args.seeds.split(",")):
        out = harness.run_cell(
            bench, args.workload, seed=seed, seconds=args.seconds,
            trace=False, started=time.perf_counter(), device=dev,
            peaks=harness.peaks_for(dev.device_kind), control=True,
            work=os.path.join(harness.WORK, f"control-{seed}"))
        failed_all &= not out["correct"]
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control_correct": out["correct"],
                          "checks": out["checks"],
                          "program_checks": out["program_checks"],
                          "reads": out["reads"]}), flush=True)
    return 0 if failed_all else 3


if __name__ == "__main__":
    sys.exit(main())
