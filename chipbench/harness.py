"""Run one cell of BENCHMARK.json once and build its result line.

Everything particular to a cell is found by name: the configuration's file
(``configs``' ``file``) names its ``kind``, built by
``chipbench.kinds.<kind>``; the mix is ``chipbench/traffic/<traffic>.json``
and names its ``loop``, ``chipbench.loops.<loop>``, which checks the mix's
keys (``check``) and runs it; each metric is read by
``chipbench.metrics.<name>``. A cell of a new shape, mix, counter or span
is new files there, and no edit here.

Each run builds one record of its window, ``rec``, which every metric's
reader takes. Besides the window's reads, bytes, latencies and compiles it
carries three tables of window deltas:

* ``io``: every numeric field of the store's ``ReadStats``
  (``dataclasses.fields``; not the latency histogram or the lock);
* ``counters``: the kind's own counters, ``Built.counters()`` (empty where
  the kind gives none);
* ``spans``: with ``trace``, the store's span table
  (``repro.lake.spans.snapshot()``: per name ``count``, ``total_s``,
  ``self_s``), spans turned on before warm-up; without, spans stay off
  and it is None.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import os
import shutil
import sys
from contextlib import nullcontext
from typing import Any, Dict, List, Optional, Tuple

import jax

from . import check, idle_by_span
from . import trace as tracing

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
WORK = os.path.join(CHECKOUT, ".chipbench")
# mixes, relative to the checkout
TRAFFIC = os.path.join("chipbench", "traffic")
# span table columns, as deltas over the window
SPAN_COLUMNS = ("count", "total_s", "self_s")


class Compiles:
    """Counts programs that miss JAX's in-memory cache (compiled here or
    loaded from the persistent cache), through ``jax.monitoring``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_: Any) -> None:
        if event == self.EVENT:
            self.count += 1


def load_json(path: str) -> Dict[str, Any]:
    """A JSON file's object."""
    with open(path) as f:
        return json.load(f)


def loop_of(mix: Dict[str, Any]) -> Any:
    """The module that runs ``mix``: ``chipbench.loops.<loop>``."""
    if "loop" not in mix:
        raise ValueError("the mix names no 'loop'")
    return importlib.import_module(f"chipbench.loops.{mix['loop']}")


def cell(bench: Dict[str, Any], name: str, trace: bool
         ) -> Tuple[Dict[str, Any], Dict[str, Any], Dict[str, Any],
                    List[Dict[str, Any]]]:
    """(workload, configuration, mix, metrics) of the cell ``name``; the
    mix is checked by its loop."""
    wl = next((w for w in bench["workloads"] if w["name"] == name), None)
    if wl is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == wl["config"])
    cfg = load_json(os.path.join(CHECKOUT, entry["file"]))
    mix = load_json(os.path.join(CHECKOUT, TRAFFIC, wl["traffic"] + ".json"))
    loop_of(mix).check(mix)
    kind = "per_layer" if trace else "end_to_end"
    metrics = [m for m in bench[kind]
               if name in m.get("workloads", [name])]
    return wl, cfg, mix, metrics


def dir_bytes(root: str) -> int:
    """Bytes of every file under ``root``."""
    return sum(os.path.getsize(os.path.join(d, f))
               for d, _, files in os.walk(root) for f in files)


def io_snapshot(store: Any) -> Dict[str, float]:
    """Every numeric counter of the store's ``ReadStats``, by field name."""
    stats = store.io.stats
    out = {}
    for f in dataclasses.fields(stats):
        v = getattr(stats, f.name)
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            out[f.name] = v
    return out


def delta(before: Dict[str, float], after: Dict[str, float]
          ) -> Dict[str, float]:
    """``after - before`` of each counter both hold."""
    return {k: v - before[k] for k, v in after.items() if k in before}


def span_delta(before: Dict[str, Dict[str, float]],
               after: Dict[str, Dict[str, float]]
               ) -> Dict[str, Dict[str, float]]:
    """The span table's growth: each name that ran in between."""
    out = {}
    for name, row in after.items():
        was = before.get(name, {})
        d = {k: row[k] - was.get(k, 0) for k in SPAN_COLUMNS}
        if d["count"]:
            out[name] = d
    return out


def peaks_for(kind: str) -> Dict[str, float]:
    """The chip's peaks; an unknown ``device_kind`` is an error."""
    table = load_json(os.path.join(HERE, "peaks.json"))["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def _profile_options() -> Any:
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def run_cell(bench: Dict[str, Any], name: str, *, trace: bool,
             **kw: Any) -> Dict[str, Any]:
    """One run of the cell ``name`` of ``bench``; see :func:`run`."""
    _, cfg, mix, metrics = cell(bench, name, trace)
    return run(cfg, mix, metrics, trace=trace, **kw)


def run(cfg: Dict[str, Any], mix: Dict[str, Any],
        metrics: List[Dict[str, Any]], *, seed: int, seconds: float,
        trace: bool, started: float, device: Any,
        peaks: Optional[Dict[str, float]] = None, control: bool = False,
        work: str = WORK) -> Dict[str, Any]:
    """One run of a configuration under a mix; returns the result line.

    ``started`` is the process's start on ``time.perf_counter``'s clock,
    ``device`` the chip the reads must land on, ``work`` the directory the
    store and the trace are written under (and removed from).
    ``control=True`` compares the lower-precision reference in the
    program's place, on the same kept reads; the program's own numbers
    then go under ``program_checks``.
    """
    compiles = Compiles()
    kinds = importlib.import_module(f"chipbench.kinds.{cfg['kind']}")
    loop = loop_of(mix)
    if trace:
        from repro.lake import spans
    store_root = os.path.join(work, "store")
    trace_dir = os.path.join(work, "trace")
    for d in (store_root, trace_dir):
        shutil.rmtree(d, ignore_errors=True)
    try:
        built = kinds.build(cfg, seed, store_root)
        stored = dir_bytes(store_root)
        counters = built.counters or dict
        if trace:
            spans.enable(True)
        loop.warm(built, mix, seed)
        if trace:
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=_profile_options())
        io0, k0, c0 = io_snapshot(built.store), counters(), compiles.count
        s0 = spans.snapshot() if trace else None
        mark = ((lambda: jax.profiler.TraceAnnotation(tracing.WINDOW))
                if trace else nullcontext)
        window = loop.run(built, mix, seed, seconds, mark=mark)
        in_window = compiles.count - c0
        io1, k1 = io_snapshot(built.store), counters()
        table = span_delta(s0, spans.snapshot()) if trace else None
        summary, by_span = None, None
        if trace:
            jax.profiler.stop_trace()
            path = tracing.find(trace_dir)
            if path:
                summary = tracing.reduce(path)
                by_span = idle_by_span.idle_by_span(path)
        stats = device.memory_stats() or {}
        kept = check.host_copies(window, device)
        built.store.io.cache.clear()
        numbers = check.compare(built, window, kept)
        if control:
            program, numbers = numbers, check.compare(built, window, kept,
                                                      control=True)
    finally:
        if trace:
            spans.enable(False)
        for d in (store_root, trace_dir):
            shutil.rmtree(d, ignore_errors=True)
    rec = {"reads": window.reads, "window_s": window.seconds,
           "bytes": window.bytes, "latencies": window.latencies,
           "setup_s": window.start - started, "stored_bytes": stored,
           "logical_bytes": built.logical_bytes,
           "io": delta(io0, io1), "counters": delta(k0, k1), "spans": table,
           "compiles": in_window, "kernel_bytes": window.kernel_bytes,
           "trace": summary, "peaks": peaks}
    values = {}
    for m in metrics:
        v = importlib.import_module(f"chipbench.metrics.{m['name']}").read(rec)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    out: Dict[str, Any] = {
        "correct": check.passed(numbers), "attempted": window.attempted,
        "failed": window.failed, "metrics": values,
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "count": len(jax.devices()),
                   "memory_peak_bytes": stats.get("peak_bytes_in_use")}}
    if trace:
        out["device"]["busy_s"] = summary.busy_s if summary else 0.0
        out["device"]["window_s"] = (summary.window_s if summary
                                     else window.seconds)
        if summary:
            out["breakdown"] = {
                "device_ops": [list(x) for x in summary.device_ops],
                "idle_gaps": [list(x) for x in summary.idle_gaps],
                "idle_by_span": [list(x) for x in by_span]}
    out["reads"] = {"completed": window.reads, "window_s": window.seconds,
                    "mean_ms": (1e3 * sum(window.latencies) / window.reads
                                if window.reads else None),
                    "checked": len(kept), "compiles_before_window": c0,
                    "errors": window.errors}
    if control:
        out["program_checks"] = program
    out["checks"] = numbers
    for k, n in numbers.items():
        print(f"check {k} {n['value']} limit {n['limit']}", file=sys.stderr)
    return out
