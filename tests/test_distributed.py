"""Multi-device behaviour via subprocesses (main test process keeps 1 device).

Covers: halo message passing ≡ dense oracle, distributed gather-scatter
Laplacian ≡ single-device GS, and RSB-partition-aware halo volume < naive
partition halo volume (the paper's framework integration).
"""

import os
import subprocess
import sys


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str, devices: int = 8, timeout: int = 420) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=timeout, env=env,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


def test_halo_matvec_and_rsb_volume():
    run_sub(r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, PartitionSpec as P
from repro.mesh.graphs import grid_graph_2d
from repro.core.rcb import rcb_parts
from repro.core.rsb import rsb_partition_graph
from repro.dist.partition_aware import plan_halo_sharding, adjacency_matvec_distributed

g = grid_graph_2d(16, 16)
coords = np.stack(np.meshgrid(np.arange(16), np.arange(16), indexing='ij'), -1)
coords = np.concatenate([coords.reshape(-1, 2), np.zeros((256, 1))], 1).astype(float)

# dense oracle
A = np.zeros((256, 256)); A[g.rows, g.indices] = g.weights
x = np.random.default_rng(0).normal(size=256)

mesh = jax.make_mesh((8,), ("shards",), axis_types=(AxisType.Auto,))
for parts in (rcb_parts(coords, 8), np.random.default_rng(1).integers(0, 8, 256)):
    # rebalance random parts to equal sizes for planning
    plan = plan_halo_sharding(g, parts, 8)
    with jax.set_mesh(mesh):
        y = adjacency_matvec_distributed(plan, mesh, x)
    assert np.abs(y - A @ x).max() < 1e-4, "halo matvec mismatch"

# RSB halo < random-partition halo (paper's min-cut objective -> less comm)
p_rsb, _ = rsb_partition_graph(g, 8, tol=1e-3)
p_rnd = np.random.default_rng(2).permutation(np.arange(256) % 8)
h_rsb = plan_halo_sharding(g, p_rsb, 8).halo
h_rnd = plan_halo_sharding(g, p_rnd, 8).halo
print("halo rsb", h_rsb, "rnd", h_rnd)
assert h_rsb < h_rnd
print("OK")
""")


def test_distributed_gs_laplacian():
    run_sub(r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, PartitionSpec as P
from repro.mesh import box_mesh
from repro.core import weighted_laplacian
from repro.core.gather_scatter import gs_setup
from repro.dist.collectives import dist_lap_apply_allreduce

m = box_mesh(4, 4, 4)
L = weighted_laplacian(m.vert_gid)
x = np.random.default_rng(1).normal(size=64).astype(np.float32)
y_ref = np.asarray(L.apply(jnp.asarray(x)))
h = gs_setup(m.vert_gid)
gid = np.asarray(h.gid).reshape(8, 8, 8)
deg = np.asarray(L.degree_full).reshape(8, 8)
mesh = jax.make_mesh((8,), ("shards",), axis_types=(AxisType.Auto,))
def fn(g, xl, d):
    return dist_lap_apply_allreduce(g[0], xl[0], d[0], h.n_global, "shards")[None]
with jax.set_mesh(mesh):
    out = jax.shard_map(fn, mesh=mesh, in_specs=(P("shards"),)*3,
                        out_specs=P("shards"))(
        jnp.asarray(gid), jnp.asarray(x.reshape(8, 8)), jnp.asarray(deg))
assert np.abs(np.asarray(out).reshape(-1) - y_ref).max() < 1e-4
print("OK")
""")
