"""The model-hub cell on the CPU at a tiny width: its kind, open loop, mix
and three readers run through ``harness.run_cell`` and come out correct;
its control does not."""

import dataclasses
import json
import time

import jax
import numpy as np
import pytest

from chipbench import harness
from chipbench.data import hub
from chipbench.kinds import model_hub
from chipbench.loops import open as open_loop
from chipbench.metrics import coalesced_pct, gateway_queue_ms_per_load
from chipbench.metrics import undelta_ms_per_load

from .conftest import load

CELL = "hub-phi3-variants"
SEED = 2**31 + 41
TINY = dict(n_layers=2, d_model=32, n_heads=2, n_kv_heads=2, head_dim=16,
            d_ff=64, vocab_size=96)


@pytest.fixture
def tiny_hub(bench, monkeypatch, tmp_path):
    """The bench with the cell's configuration and mix at a tiny width, in
    a checkout at ``tmp_path``; a fast rate, so a short window holds
    several loads."""
    monkeypatch.setattr(harness, "CHECKOUT", str(tmp_path))
    entry = next(c for c in bench["configs"]
                 if c["name"] == "hub-phi3-mini-variants")
    wl = next(w for w in bench["workloads"] if w["name"] == CELL)
    cfg = load(entry["file"])
    cfg.update(TINY)
    mix = load(f"{harness.TRAFFIC}/{wl['traffic']}.json")
    mix.update(rate_per_s=40.0)
    (tmp_path / entry["file"]).parent.mkdir(parents=True)
    (tmp_path / entry["file"]).write_text(json.dumps(cfg))
    (tmp_path / harness.TRAFFIC).mkdir(parents=True)
    (tmp_path / harness.TRAFFIC / f"{wl['traffic']}.json").write_text(
        json.dumps(mix))
    return bench, cfg, mix, tmp_path


def _run(bench, root, trace, **kw):
    return harness.run_cell(bench, CELL, trace=trace, seed=SEED, seconds=0.5,
                            started=time.perf_counter(),
                            device=jax.devices()[0],
                            peaks={"hbm_bytes_per_s": 819e9},
                            work=str(root / "work"), **kw)


@pytest.mark.parametrize("trace", [False, True])
def test_hub_cell_runs_correct(tiny_hub, trace):
    bench, cfg, mix, root = tiny_hub
    out = _run(bench, root, trace)
    assert out["correct"], (out["checks"], out["reads"]["errors"])
    assert out["failed"] == 0
    assert out["reads"]["checked"] == len(mix["check"]) == 3
    got = out["metrics"]
    n = out["reads"]["completed"]
    assert n == out["attempted"] > len(mix["check"])
    if not trace:
        assert set(got) == {"read_GBps", "read_p95_ms",
                            "stored_bytes_per_byte", "setup_s"}
        assert got["read_GBps"]["value"] == pytest.approx(
            n * hub.tree_bytes(cfg) / out["reads"]["window_s"] / 1e9)
        assert got["stored_bytes_per_byte"]["value"] < 1
        return
    assert set(got) == {"undelta_ms_per_load", "gateway_queue_ms_per_load",
                        "coalesced_pct"}
    assert got["undelta_ms_per_load"]["value"] > 0
    assert got["gateway_queue_ms_per_load"]["value"] >= 0
    assert 0 <= got["coalesced_pct"]["value"] < 100


def test_hub_cell_control_is_not_correct(tiny_hub):
    bench, _, _, root = tiny_hub
    out = _run(bench, root, False, control=True)
    assert out["program_checks"]["mismatched_elements"]["value"] == 0
    assert not out["correct"]
    assert out["checks"]["mismatched_elements"]["value"] > 0


def test_open_loop_rejects_a_mix_without_its_keys(tiny_hub):
    _, _, mix, _ = tiny_hub
    open_loop.check(mix)
    for key in open_loop.KEYS:
        with pytest.raises(ValueError, match=key):
            open_loop.check({k: v for k, v in mix.items() if k != key})
    with pytest.raises(ValueError, match="dora"):
        open_loop.check(dict(mix, check=["base", "dora"]))


def test_every_seed_checks_a_base_a_lora_and_a_full_load(bench):
    # the cell's own schedule over the benchmark's window holds each group,
    # and each seed keeps one request of each, so three distinct flights
    wl = next(w for w in bench["workloads"] if w["name"] == CELL)
    mix = load(f"{harness.TRAFFIC}/{wl['traffic']}.json")
    plan = open_loop.schedule(mix, bench["run_seconds"])
    picks = set()
    for seed in range(SEED, SEED + 200):
        keep = open_loop.kept(plan, mix["check"], seed)
        models = sorted(plan[i][2] for i in keep)
        assert [m.rstrip("0123456789") for m in models] == ["base", "full",
                                                            "lora"]
        picks.add(tuple(sorted(keep)))
    assert len(picks) > 1  # the seed draws within each group
    assert open_loop.kept(plan, mix["check"], SEED) == open_loop.kept(
        plan, mix["check"], SEED)
    with pytest.raises(ValueError, match="full"):
        open_loop.kept([p for p in plan if not p[2].startswith("full")],
                       mix["check"], SEED)


def test_open_loop_schedule_is_fixed_and_zipf():
    mix = load(f"{harness.TRAFFIC}/hub-zipf-poisson.json")
    mix.update(rate_per_s=50.0)
    plan = open_loop.schedule(mix, 40.0)
    assert plan == open_loop.schedule(mix, 40.0)
    times = [t for t, _, _ in plan]
    assert times == sorted(times) and times[-1] < 40.0
    assert len(plan) == pytest.approx(2000, rel=0.1)
    models = [m for _, _, m in plan]
    share = models.count("base") / len(models)
    ranks = np.arange(1, 10, dtype=float) ** -mix["zipf_s"]
    assert share == pytest.approx(ranks[0] / ranks.sum(), abs=0.04)
    assert models.count("base") > models.count("lora5")
    assert {t for _, t, _ in plan} == set(range(mix["tenants"]))


def test_a_shed_request_is_failed(tiny_hub, tmp_path):
    from repro.serve import Gateway, TenantPolicy

    _, cfg, mix, _ = tiny_hub
    built = model_hub.build(cfg, SEED, str(tmp_path / "store"))
    # one load at a time and no queue: requests that arrive while a load
    # runs are shed
    built = dataclasses.replace(built, gateway=Gateway(
        built.store, max_inflight=1,
        default_policy=TenantPolicy(max_inflight=1, queue_limit=0)))
    w = open_loop.run(built, dict(mix, rate_per_s=400.0), SEED, 0.2)
    assert w.attempted == len(open_loop.schedule(dict(mix, rate_per_s=400.0),
                                                 0.2))
    assert w.failed > 0 and w.reads > 0
    assert w.failed + w.reads == w.attempted
    assert any("RetryAfter" in e for e in w.errors)


def test_leaves_match_the_program(tiny_hub):
    from repro.models import transformer
    from repro.models.config import get_arch
    import repro.configs  # noqa: F401  (registers the architectures)

    _, cfg, _, _ = tiny_hub
    arch = dataclasses.replace(get_arch(cfg["model"]), **TINY)
    shapes = jax.eval_shape(lambda k: transformer.init_params(arch, k),
                            jax.random.PRNGKey(0))
    got = jax.tree_util.tree_map(lambda x: (tuple(x.shape), str(x.dtype)),
                                 shapes)
    want = hub.nest({k: (s, "bfloat16") for k, s in hub.leaves(cfg).items()})
    assert got == want
    full = load("chipbench/configs/hub-phi3-mini-variants.json")
    assert hub.tree_bytes(full) == 847_017_984
    assert len(hub.leaves(full)) == 12


def test_variants_differ_from_the_base_where_they_should(tiny_hub):
    _, cfg, _, _ = tiny_hub
    base = hub.base_tree(cfg, SEED)
    for name, spec in cfg["variants"].items():
        var = hub.variant_tree(cfg, SEED, name, base)
        for leaf, x in base.items():
            changed = np.mean(x.view(np.uint16) != var[leaf].view(np.uint16))
            touched = (spec["kind"] == "full"
                       or leaf.rsplit("/", 1)[-1] in spec["targets"])
            assert (changed > 0.5) if touched else (changed == 0), (name, leaf)
    again = hub.tree(cfg, SEED, "lora3")
    assert jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a, b: bool((a.view(np.uint16) == b.view(np.uint16)).all()),
        again, hub.nest(hub.variant_tree(cfg, SEED, "lora3", base))))


@pytest.mark.parametrize("reader", [undelta_ms_per_load,
                                    gateway_queue_ms_per_load, coalesced_pct])
def test_hub_readers_find_nothing_without_their_counters(reader):
    rec = {"spans": None, "counters": {}, "reads": 3}
    assert reader.read(rec) is None
    rec = {"spans": {}, "counters": {"loads_run": 0, "queue_wait_s": 0.0,
                                     "coalesced": 0, "requests": 0},
           "reads": 0}
    assert reader.read(rec) is None


def test_hub_readers_read_their_counters():
    rec = {"spans": {"store.undelta": {"count": 8, "total_s": 0.5,
                                       "self_s": 0.4}},
           "counters": {"loads_run": 4, "queue_wait_s": 2.0, "coalesced": 1,
                        "requests": 5}}
    assert undelta_ms_per_load.read(rec) == pytest.approx(100.0)
    assert gateway_queue_ms_per_load.read(rec) == pytest.approx(500.0)
    assert coalesced_pct.read(rec) == pytest.approx(20.0)


def test_the_kind_fails_fast_without_a_device_load(monkeypatch, tmp_path):
    from repro.serve import ModelRepo

    def host_only(self, template):
        raise AssertionError("not reached")

    monkeypatch.setattr(ModelRepo, "load", host_only)
    cfg = load("chipbench/configs/hub-phi3-mini-variants.json")
    t0 = time.perf_counter()
    with pytest.raises(RuntimeError, match="device"):
        model_hub.build(cfg, SEED, str(tmp_path / "store"))
    assert time.perf_counter() - t0 < 5
    assert not (tmp_path / "store").exists()
