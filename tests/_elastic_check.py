"""Subprocess check: elastic restart — checkpoint saved on one device
layout restores onto a different mesh with resharding (8 forced devices)."""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys
import tempfile

import jax

from repro.launch.mesh import make_mesh
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.runtime.checkpoint import CheckpointManager


def main() -> int:
    assert len(jax.devices()) == 8
    tmp = tempfile.mkdtemp()
    mgr = CheckpointManager(tmp, async_save=False)

    # "training" ran on a (8,) data-only mesh
    mesh_a = make_mesh((8,), ("data",))
    w = jax.device_put(jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
                       NamedSharding(mesh_a, P("data", None)))
    mgr.save(7, {"w": w}, block=True)

    # restart lands on a (2, 4) data×model mesh — reshard on restore
    mesh_b = make_mesh((2, 4), ("data", "model"))
    target = {"w": jnp.zeros((8, 8), jnp.float32)}
    sh = {"w": NamedSharding(mesh_b, P("data", "model"))}
    step, restored = mgr.restore(target, shardings=sh)
    assert step == 7
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(w))
    assert restored["w"].sharding == sh["w"]
    print("elastic reshard-on-restore: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
