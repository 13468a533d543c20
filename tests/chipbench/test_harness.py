"""The harness finds every cell's files by name, and runs a cell end to end
on the CPU at a tiny size (the look for a chip is in run.py, not here)."""

import importlib
import os
import subprocess
import sys
import time
import types

import jax
import pytest

from chipbench import harness, loadgen
from chipbench.loops import closed as closed_loop

from .conftest import ROOT


def test_every_cell_resolves_by_name(bench):
    for wl in bench["workloads"]:
        for trace in (False, True):
            w, cfg, mix, metrics = harness.cell(bench, wl["name"], trace)
            assert w is wl and cfg["name"] == wl["config"]
            importlib.import_module(f"chipbench.kinds.{cfg['kind']}")
            loop = importlib.import_module(f"chipbench.loops.{mix['loop']}")
            assert harness.loop_of(mix) is loop
            loop.check(mix)
            for fn in ("warm", "run"):
                assert callable(getattr(loop, fn))
            assert metrics
            for m in metrics:
                mod = importlib.import_module(f"chipbench.metrics.{m['name']}")
                assert callable(mod.read)
        names = [m["name"] for m in bench["end_to_end"]
                 if wl["name"] in m.get("workloads", [wl["name"]])]
        assert "setup_s" in names and len(names) >= 2


def test_closed_loop_cells_have_decks(bench):
    closed = 0
    for wl in bench["workloads"]:
        _, cfg, mix, _ = harness.cell(bench, wl["name"], False)
        if mix["loop"] != "closed":
            continue
        closed += 1
        shape = cfg.get("shape") or [cfg["rows"], *cfg["row_shape"]]
        assert loadgen.starts(mix, shape)
        for key in closed_loop.KEYS:
            with pytest.raises(ValueError, match=key):
                closed_loop.check({k: v for k, v in mix.items() if k != key})
    assert closed


def test_config_files_match_benchmark(bench):
    for c in bench["configs"]:
        cfg = harness.load_json(os.path.join(ROOT, c["file"]))
        assert cfg["name"] == c["name"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])


def test_unknown_names_are_errors(bench):
    with pytest.raises(KeyError):
        harness.cell(bench, "no-such-cell", False)
    with pytest.raises(KeyError):
        harness.peaks_for("TPU v0 imaginary")
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_mix_decks_are_the_same_set_for_every_seed():
    mix = {"clients": 2, "warmup": 1, "check_per_client": 1,
           "slice": [{"start": [3, 9], "length": 2}]}
    deck = loadgen.starts(mix, [12])
    assert deck[0] == ((3, 5),) and deck[-1] == ((9, 11),)
    for seed in (0, 2**31 + 7):
        got = loadgen.stream(mix, [12], seed, 1)
        first = [next(got) for _ in range(len(deck))]
        assert sorted(first) == deck
    again = loadgen.stream(mix, [12], 2**31 + 7, 1)
    assert [next(again) for _ in range(len(deck))] == first
    with pytest.raises(ValueError):
        loadgen.starts(mix, [10])


@pytest.mark.parametrize("which", ["tiny_dense", "tiny_sparse"])
@pytest.mark.parametrize("trace", [False, True])
def test_a_run_on_cpu_is_correct(request, bench, tmp_path, which, trace):
    cfg, mix = request.getfixturevalue(which)
    metrics = bench["per_layer" if trace else "end_to_end"]
    out = harness.run(cfg, mix, metrics, seed=2**33 + 5, seconds=0.3,
                      trace=trace, started=time.perf_counter(),
                      device=jax.devices()[0],
                      peaks={"hbm_bytes_per_s": 819e9}, work=str(tmp_path))
    assert out["correct"], out["checks"]
    assert out["attempted"] >= out["reads"]["completed"] > 0
    assert out["reads"]["checked"] > 0
    assert list(out)[-1] == "checks"
    if not trace:
        assert set(out["metrics"]) == {m["name"] for m in metrics}
    else:
        assert "busy_s" in out["device"] and "window_s" in out["device"]
    assert not os.listdir(tmp_path)


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        "dense-ffhq-batch16", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs 1 TPU" in p.stderr


def test_run_fails_in_a_directory_of_only_the_benchmark(tmp_path):
    import json
    import shutil

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in bench["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        "sparse-uber-day", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""


@pytest.mark.parametrize("trace", [False, True])
def test_the_record_carries_every_counter_and_traced_spans(
        monkeypatch, bench, tmp_path, tiny_dense, trace):
    from repro.lake import spans

    seen = []
    spy = types.ModuleType("chipbench.metrics.spy_record")
    spy.read = lambda rec: seen.append(rec)
    monkeypatch.setitem(sys.modules, spy.__name__, spy)
    cfg, mix = tiny_dense
    before = spans.snapshot()
    out = harness.run(cfg, mix, [{"name": "spy_record", "unit": "count"}],
                      seed=2**31 + 11, seconds=0.3, trace=trace,
                      started=time.perf_counter(), device=jax.devices()[0],
                      peaks={"hbm_bytes_per_s": 819e9}, work=str(tmp_path))
    assert out["correct"], out["checks"]
    (rec,) = seen
    io = rec["io"]
    for key in ("gets", "cache_hits", "cache_misses", "frames_decoded",
                "frame_bytes_wire", "frame_bytes_decoded", "decode_s",
                "fetch_wait_s", "decode_queue_s", "bytes_to_device"):
        assert key in io
    assert "latency" not in io and "_lock" not in io
    assert io["cache_hits"] + io["cache_misses"] > 0
    assert io["bytes_to_device"] >= rec["bytes"] > 0
    assert rec["counters"] == {}
    if trace:
        parse = rec["spans"]["store.parse"]
        assert parse["count"] > 0 and 0 < parse["self_s"] <= parse["total_s"]
        assert {"store.stage", "store.h2d"} <= set(rec["spans"])
        ranked = out["breakdown"]["idle_by_span"]
        assert ranked
        assert {n for n, _ in ranked} <= {"any"} | set(rec["spans"])
        assert spans.span("store.parse") is spans.span("store.stage")
    else:
        assert rec["spans"] is None
        assert spans.snapshot() == before
        assert "breakdown" not in out
