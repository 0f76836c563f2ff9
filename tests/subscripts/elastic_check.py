"""Subprocess: elastic checkpoint restore across different mesh shapes.

Saves a sharded train state on a (4, 2) mesh, restores it onto a (2, 4)
mesh (different device assignment), and checks values are identical.
"""
import os
import sys
import tempfile

# forced host devices exist only on the CPU backend; pinning it also
# keeps this child off any chip its parent process holds
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.checkpoint.manager import CheckpointManager  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import compat, partition  # noqa: E402
from repro.models import lm  # noqa: E402


def mesh_of(shape):
    return compat.make_mesh(shape, ("data", "model"))


def main():
    cfg = get_config("tinyllama-1.1b").reduced()
    params = lm.init(cfg, jax.random.PRNGKey(0))

    mesh_a = mesh_of((4, 2))
    sh_a = partition.param_shardings(params, cfg, mesh_a, fsdp=True)
    params_a = jax.tree_util.tree_map(jax.device_put, params, sh_a)

    d = tempfile.mkdtemp()
    mgr = CheckpointManager(d)
    mgr.save(7, params_a)

    # "restart" on a different mesh
    mesh_b = mesh_of((2, 4))
    sh_b = partition.param_shardings(params, cfg, mesh_b, fsdp=True)
    like = lm.init_abstract(cfg)
    restored = mgr.restore(7, like, shardings=sh_b)

    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # restored leaves actually live on the new mesh sharding
    leaf = jax.tree_util.tree_leaves(restored)[0]
    assert leaf.sharding.mesh.shape["model"] == 4
    print("ELASTIC_OK")


if __name__ == "__main__":
    main()
