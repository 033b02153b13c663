"""Int8 gradient-compression collective (cross-pod trick)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.optim.compress import (dequantize, quantize, quantized_psum,
                                  quantized_psum_tree)


def test_quantize_roundtrip_error_bound():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(256, 64)), jnp.float32)
    q, scale = quantize(x)
    back = dequantize(q, scale)
    assert float(jnp.abs(back - x).max()) <= 0.5 * float(scale) + 1e-7


def test_quantized_psum_matches_psum():
    """shard_map on a 1-wide 'pod' axis: compressed == exact psum up to
    quantization error."""
    mesh = jax.make_mesh((1,), ("pod",))
    x = jnp.asarray(np.random.default_rng(1).normal(size=(64,)), jnp.float32)

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=P(),
                       out_specs=P())
    def f(v):
        return quantized_psum(v, "pod")

    out = f(x)
    err = float(jnp.abs(out - x).max())
    assert err <= float(jnp.max(jnp.abs(x))) / 127.0 + 1e-6


def test_quantized_psum_simulated_pods():
    """Simulate 4 pods' partial gradients: compressed sum within the
    analytic error bound of the exact sum."""
    rng = np.random.default_rng(2)
    parts = [jnp.asarray(rng.normal(size=(128,)), jnp.float32)
             for _ in range(4)]
    exact = sum(parts)
    scale = max(float(jnp.max(jnp.abs(p))) for p in parts) / 127.0
    total = sum(quantize(p, jnp.float32(scale))[0].astype(jnp.int32)
                for p in parts)
    approx = total.astype(jnp.float32) * scale
    assert float(jnp.abs(approx - exact).max()) <= 0.5 * scale * 4 + 1e-6


def test_tree_version():
    tree = {"a": jnp.ones((8,)), "b": {"c": jnp.full((4,), -2.0)}}
    mesh = jax.make_mesh((1,), ("pod",))

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(P(),),
                       out_specs=P())
    def f(t):
        return quantized_psum_tree(t, "pod")

    out = f(tree)
    np.testing.assert_allclose(np.asarray(out["b"]["c"]), -2.0, atol=0.02)
