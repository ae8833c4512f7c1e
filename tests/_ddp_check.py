"""Subprocess check: int8 error-feedback compressed DDP vs exact gradients
(8 forced host devices)."""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys

import jax

from repro.launch.mesh import make_mesh
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.distributed.compression import compressed_psum


def main() -> int:
    assert len(jax.devices()) == 8
    mesh = make_mesh((8,), ("data",))
    rng = np.random.default_rng(0)
    dim = 512
    w = jnp.asarray(rng.normal(size=(dim,)), jnp.float32)
    xs = jnp.asarray(rng.normal(size=(64, dim)), jnp.float32)   # 8 per shard
    ys = xs @ np.asarray(rng.normal(size=(dim,)), np.float32)

    def loss(w_, x_, y_):
        return jnp.mean((x_ @ w_ - y_) ** 2)

    def exact_step(w_, x_, y_):
        g = jax.grad(loss)(w_, x_, y_)
        return jax.lax.pmean(g, "data")

    def compressed_step(w_, x_, y_, err):
        g = jax.grad(loss)(w_, x_, y_)
        mean, new_err = compressed_psum({"g": g}, "data", {"g": err[0]})
        return mean["g"], new_err["g"][None]

    f_exact = jax.jit(jax.shard_map(
        exact_step, mesh=mesh, in_specs=(P(), P("data"), P("data")),
        out_specs=P(), check_vma=False))
    f_comp = jax.jit(jax.shard_map(
        compressed_step, mesh=mesh,
        in_specs=(P(), P("data"), P("data"), P("data", None)),
        out_specs=(P(), P("data", None)), check_vma=False))

    # SGD runs: compressed-with-EF must track exact within tolerance.
    lr = 0.05
    w_e = w_c = w
    err = jnp.zeros((8, dim), jnp.float32)   # per-shard error-feedback state
    for step in range(60):
        w_e = w_e - lr * f_exact(w_e, xs, ys)
        g_c, err = f_comp(w_c, xs, ys, err)
        w_c = w_c - lr * g_c
    l_e = float(loss(w_e, xs, ys))
    l_c = float(loss(w_c, xs, ys))
    print(f"exact loss {l_e:.6f}  compressed+EF loss {l_c:.6f}")
    assert l_c < 1.5 * l_e + 1e-3, (l_e, l_c)
    drift = float(jnp.linalg.norm(w_e - w_c) / jnp.linalg.norm(w_e))
    print(f"weight drift {drift:.4f}")
    assert drift < 0.05
    print("compressed DDP with error feedback tracks exact: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
