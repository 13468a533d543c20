"""Compile the read path's device programs for a described TPU v5e chip.

Nothing runs: these compiles raise what the chip's compiler would raise
(unaligned blocks, VMEM overruns, programs too large for HBM), at the
shapes the paper's §V reads use. Kernels are called directly with
``interpret=False``, since the dispatch in ``kernels.ops`` sees this
process's CPU backend. FTSF device reads dispatch no kernel
(``ChunkAssembler`` makes one ``device_put``), so they have nothing to
compile here.
"""

import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.unshuffle import byte_unshuffle_planes

UBER_SLICE = (1, 24, 1140, 1717)    # one X[i] of the paper's Uber tensor
TILE = 512


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


# the Uber reads' K per day spans 16,252-20,002; 100,000 is far past it
@pytest.mark.parametrize("k,dtype", [(17_800, jnp.float32),
                                     (20_002, jnp.float32),
                                     (20_002, jnp.bfloat16),
                                     (100_000, jnp.float32)])
def test_coo_scatter_compiles_for_uber_slice(one_chip, k, dtype):
    compiled = _compile(lambda i, v: ops.coo_scatter(i, v, UBER_SLICE),
                        one_chip, ((k,), jnp.int32), ((k,), dtype))
    text = compiled.as_text()
    assert "scatter" in text
    # XLA lowers the scatter to a flat one and lays the result out in one
    # pass into the tiled output (1717 lanes pad to 1792): no crop, no
    # chunked relayout loop, at most one slice-sized temporary
    assert " while(" not in text and "dynamic-update-slice" not in text
    mem = compiled.memory_analysis()
    slice_bytes = math.prod(UBER_SLICE) * jnp.dtype(dtype).itemsize
    assert slice_bytes <= mem.output_size_in_bytes < 1.05 * slice_bytes
    assert mem.temp_size_in_bytes < 1.01 * slice_bytes


@pytest.mark.parametrize("itemsize", [2, 4, 8])
def test_unshuffle_compiles(one_chip, itemsize):
    compiled = _compile(
        lambda p: byte_unshuffle_planes(p, tile=TILE, interpret=False),
        one_chip, ((itemsize, 65_536), jnp.uint8))
    assert "tpu_custom_call" in compiled.as_text()
