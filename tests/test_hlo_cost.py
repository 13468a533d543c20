"""Loop-aware HLO cost model: trip-count correction vs known ground truth."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import hlo_cost


def _compile(f, *specs):
    return jax.jit(f).lower(*specs).compile()


def test_scan_trip_count_multiplies_flops():
    def f(x, w):
        def body(c, wi):
            return jnp.dot(c, wi), None
        return jax.lax.scan(body, x, w)[0]

    comp = _compile(f, jax.ShapeDtypeStruct((16, 32), jnp.float32),
                    jax.ShapeDtypeStruct((10, 32, 32), jnp.float32))
    c = hlo_cost.analyze(comp.as_text())
    expected = 10 * 2 * 16 * 32 * 32
    assert abs(c.flops - expected) / expected < 0.01
    # XLA's own analysis undercounts by the trip count
    xla = comp.cost_analysis().get("flops", 0)
    assert xla < expected / 5


def test_plain_dot_matches_xla():
    def f(a, b):
        return a @ b

    comp = _compile(f, jax.ShapeDtypeStruct((64, 128), jnp.float32),
                    jax.ShapeDtypeStruct((128, 256), jnp.float32))
    c = hlo_cost.analyze(comp.as_text())
    expected = 2 * 64 * 128 * 256
    assert abs(c.flops - expected) / expected < 0.01
    assert c.bytes >= (64 * 128 + 128 * 256 + 64 * 256) * 4


def test_nested_scan():
    def f(x, w):
        def outer(c, wi):
            def inner(c2, _):
                return jnp.tanh(c2 @ wi), None
            c2, _ = jax.lax.scan(inner, c, None, length=3)
            return c2, None
        return jax.lax.scan(outer, x, w)[0]

    comp = _compile(f, jax.ShapeDtypeStruct((8, 16), jnp.float32),
                    jax.ShapeDtypeStruct((5, 16, 16), jnp.float32))
    c = hlo_cost.analyze(comp.as_text())
    expected = 5 * 3 * 2 * 8 * 16 * 16
    assert abs(c.flops - expected) / expected < 0.05
