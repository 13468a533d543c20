"""Compile the read path's Pallas kernels for a described TPU v5e chip.

Nothing runs: these compiles raise what the chip's compiler would raise
(unaligned blocks, VMEM overruns), at the shapes the paper's §V reads use.
The kernels are called directly with ``interpret=False``, since the
dispatch in ``kernels.ops`` sees this process's CPU backend. FTSF device
reads dispatch no kernel (``ChunkAssembler`` makes one ``device_put``), so
they have nothing to compile here.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.coo_scatter import MAX_K, coo_scatter
from repro.kernels.unshuffle import byte_unshuffle_planes

UBER_SLICE = 24 * 1140 * 1717    # one X[i] of the paper's Uber tensor
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


def _coo(k, one_chip, dtype=jnp.float32):
    padded = -(-UBER_SLICE // TILE) * TILE
    return _compile(lambda i, v: coo_scatter(i, v, padded, tile=TILE,
                                             interpret=False),
                    one_chip, ((k,), jnp.int32), ((k,), dtype))


@pytest.mark.parametrize("k,dtype", [(17_800, jnp.float32),
                                     (MAX_K, jnp.float32),
                                     (MAX_K, jnp.bfloat16)])
def test_coo_scatter_compiles_for_uber_slice(one_chip, k, dtype):
    compiled = _coo(k, one_chip, dtype)
    assert "tpu_custom_call" in compiled.as_text()


def test_coo_scatter_refused_past_max_k(one_chip):
    with pytest.raises(Exception, match="vmem"):
        _coo(MAX_K + 1, one_chip)


@pytest.mark.parametrize("itemsize", [2, 4, 8])
def test_unshuffle_compiles(one_chip, itemsize):
    compiled = _compile(
        lambda p: byte_unshuffle_planes(p, tile=TILE, interpret=False),
        one_chip, ((itemsize, 65_536), jnp.uint8))
    assert "tpu_custom_call" in compiled.as_text()
