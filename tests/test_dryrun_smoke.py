"""Dry-run smoke: one real lower+compile on a small host-device mesh.

Runs in a subprocess because XLA_FLAGS must be set before jax initializes
(the main test process keeps its single real device, per the assignment).
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import json
import jax
from repro.launch import specs
from repro.launch.mesh import make_mesh

mesh = make_mesh((4, 4), ("data", "model"))
cell = specs.make_cell("whisper-tiny", "train_4k", mesh)
with jax.set_mesh(mesh):
    jt = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                 out_shardings=cell.out_shardings, donate_argnums=cell.donate)
    lowered = jt.lower(*cell.args)
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    from repro.analysis import hlo_cost
    c = hlo_cost.analyze(compiled.as_text())
print(json.dumps({"flops": c.flops, "bytes": c.bytes,
                  "coll": c.total_coll_bytes,
                  "xla_flops": float(cost.get("flops", 0))}))
"""


@pytest.mark.slow
def test_dryrun_cell_compiles_on_small_mesh():
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["flops"] > 1e9           # corrected flops counted
    assert rec["bytes"] > 1e8
    assert rec["flops"] > rec["xla_flops"]  # trip-count correction applied


def test_make_cell_specs_have_shardings():
    """Cheap structural check (no compile): specs build for every arch."""
    # uses the current (single-device) process only for tree structure
    import jax
    from repro.launch import specs
    from repro.models.config import list_archs
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    for arch in list_archs():
        cell = specs.make_cell(arch, "train_4k", mesh)
        n_in = len(jax.tree.leaves(cell.in_shardings))
        assert n_in == len(jax.tree.leaves(cell.args)), arch
