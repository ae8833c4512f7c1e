"""Gradient compression with error feedback: bias vanishes over steps."""

import jax

from repro.launch.mesh import make_mesh
import jax.numpy as jnp
import numpy as np

from repro.distributed.compression import _dequantize_leaf, _quantize_leaf


def test_int8_quantize_error_bound():
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.normal(size=(256,)), jnp.float32)
    codes, scale = _quantize_leaf(g)
    deq = _dequantize_leaf(codes, scale)
    assert codes.dtype == jnp.int8
    # error ≤ half a step
    assert float(jnp.max(jnp.abs(deq - g))) <= float(scale) * 0.5 + 1e-7


def test_error_feedback_accumulates_unbiased():
    """Simulated multi-worker EF loop: the long-run mean of compressed
    reductions converges to the true mean gradient (EF21 property)."""
    rng = np.random.default_rng(1)
    workers = 4
    dim = 128
    true_grads = [rng.normal(size=dim).astype(np.float32) * (i + 1)
                  for i in range(workers)]
    errors = [np.zeros(dim, np.float32) for _ in range(workers)]
    exact_mean = np.mean(true_grads, axis=0)

    acc = np.zeros(dim, np.float64)
    steps = 50
    for _ in range(steps):
        summed = np.zeros(dim, np.float64)
        for w in range(workers):
            corrected = true_grads[w] + errors[w]
            codes, scale = _quantize_leaf(jnp.asarray(corrected))
            deq = np.asarray(_dequantize_leaf(codes, scale))
            errors[w] = corrected - deq
            summed += deq
        acc += summed / workers
    # mean of compressed means ≈ exact mean (residuals stay bounded)
    np.testing.assert_allclose(acc / steps, exact_mean, rtol=0.02, atol=0.02)
    for w in range(workers):
        codes, scale = _quantize_leaf(jnp.asarray(true_grads[w]))
        assert np.abs(errors[w]).max() <= float(scale) * 2.0  # bounded residual


def test_compressed_psum_in_shard_map_degenerate():
    """axis size 1: compressed_psum reduces to quantize+dequantize."""
    from jax.sharding import PartitionSpec as P
    from repro.distributed.compression import compressed_psum
    mesh = make_mesh((1,), ("d",))
    g = {"w": jnp.asarray(np.random.default_rng(0).normal(size=(64,)),
                          jnp.float32)}

    def f(grads):
        mean, err = compressed_psum(grads, "d")
        return mean, err

    mean, err = jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=({"w": P()},),
        out_specs=({"w": P()}, {"w": P()}), check_vma=False))(g)
    np.testing.assert_allclose(np.asarray(mean["w"] + err["w"]),
                               np.asarray(g["w"]), rtol=1e-5, atol=1e-6)
