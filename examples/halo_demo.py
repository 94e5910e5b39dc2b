"""HALO hierarchical all-to-all demo (paper §V).

Runs flat vs HALO a2a on 8 XLA host devices, verifies bit-equality, and
prints the analytic Frontier-topology speedups that reproduce Fig 8.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python examples/halo_demo.py
"""

import sys

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import halo
from repro.core.comm_model import A2ACase, speedup
from repro.core.platform import FRONTIER, TPU_V5E
from repro.sharding import MeshPlan, host_mesh


def main():
    n = len(jax.devices())
    print(f"{n} devices")
    if n >= 8:
        mesh = host_mesh((1, 8, 1), ("data", "ep", "tp"))
        plan = MeshPlan(mesh=mesh, ep=8, tp=1, dp_axes=("data",))
        x = jax.random.normal(jax.random.PRNGKey(0), (64, 16, 32))

        def run(fn):
            return jax.jit(jax.shard_map(
                fn, mesh=mesh, in_specs=P("ep", None, None),
                out_specs=P("ep", None, None), check_vma=False,
            ))(x)

        flat = run(halo.flat_all_to_all)
        for g1 in (2, 4):
            h = run(lambda xl, g=g1: halo.hierarchical_all_to_all(xl, plan, g1=g))
            ok = np.allclose(np.asarray(flat), np.asarray(h))
            print(f"HALO(g1={g1}) == flat: {ok}")
    else:
        print("(run with XLA_FLAGS=--xla_force_host_platform_device_count=8 "
              "for the live equality check)")

    print("\nFig 8 reproduction — modeled HALO speedup on Frontier "
          "(4 MiB rows):")
    for nodes in (2, 4, 8, 16, 32, 64):
        case = A2ACase(nodes * FRONTIER.chips_per_node, 4 * 2**20)
        print(f"  {nodes:3d} nodes: {speedup(case, FRONTIER):5.2f}x")
    print("\nTPU analogue — inter-pod EP group (DCI slow axis):")
    for pods in (1, 2, 4):
        case = A2ACase(pods * 256, 2**20)
        print(f"  {pods} pod(s): {speedup(case, TPU_V5E):5.2f}x")


if __name__ == "__main__":
    main()
