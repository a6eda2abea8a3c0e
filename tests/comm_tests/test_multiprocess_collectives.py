"""Multi-process DEVICE collectives: real cross-process psum/allreduce_grad.

The object-plane test covers the host side of multi-host; this covers the
data plane: two `jax.distributed` processes, four virtual CPU devices
each, one global 8-device mesh whose collectives cross the process
boundary (gloo — the CPU stand-in for DCN). A full data-parallel training
run must converge identically on both processes, with gradients synced by
`comm.allreduce_grad` over the REAL multi-process mesh.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from mp_harness import assert_all_ok, run_workers

_WORKER = r"""
import os, sys
proc_id = int(sys.argv[1])
port = sys.argv[2]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
jax.config.update("jax_platforms", "cpu")
jax.distributed.initialize(
    coordinator_address=f"127.0.0.1:{port}", num_processes=2,
    process_id=proc_id)
assert jax.process_count() == 2 and len(jax.devices()) == 8

sys.path.insert(0, os.environ["REPO_ROOT"])
import numpy as np
import jax.numpy as jnp

import chainermn_tpu

from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

comm = chainermn_tpu.create_communicator("xla")
assert comm.size == 8, comm.size
assert comm.inter_size == 2 and comm.intra_size == 4, (
    comm.inter_size, comm.intra_size)
axes = comm.axis_names

# ---- model-op over the mesh: bcast_data must equalize params ------------
params = {"w": jnp.array([1.0 + proc_id]), "b": jnp.array([proc_id * 1.0])}
params = comm.bcast_data(params)
assert float(params["w"][0]) == 1.0 and float(params["b"][0]) == 0.0

# ---- bcast_data with a NON-ZERO root: the owning process is the source --
# rank 4 is the first device of process 1, so every process must end up
# with process 1's value (r4 VERDICT: root used to be silently ignored)
p2 = comm.bcast_data({"w": jnp.array([10.0 + proc_id])}, root=4)
assert float(p2["w"][0]) == 11.0, float(p2["w"][0])

# ---- intra_rank under the process=node mapping (MIGRATION.md): each
# process IS its node's only member, so intra_rank is 0 on BOTH processes
# even though they share this host — coherent with inter_rank/inter_size
# being the process index/count (checkpoint shard naming, scatter_dataset
# and rank-0 election all assume that) and with intra_rank < intra_size
assert comm.intra_rank == 0, comm.intra_rank
assert comm.inter_rank == proc_id and comm.inter_size == 2

# ---- sub-axis ranks are DENSE in [0, size); global_index keeps the old
# mesh-flat convention (bookkeeping only — never a root) ------------------
from chainermn_tpu.comm.xla import XlaCommunicator
# full mesh: the two spaces coincide (4 = first device of process 1)
assert comm.rank == 4 * proc_id == comm.global_index, (
    comm.rank, comm.global_index)
sub_ici = XlaCommunicator(mesh=comm.mesh, axes=(axes[-1],))
assert sub_ici.size == 4, sub_ici.size
# each ici-rank names a device GROUP with one member from EACH process,
# so both processes live in group 0: rank 0 on both, strictly < size
# (the old convention returned 4 on process 1 — out of range as a root)
assert sub_ici.rank == 0, sub_ici.rank
assert sub_ici.global_index == 4 * proc_id, sub_ici.global_index
sub_dcn = XlaCommunicator(mesh=comm.mesh, axes=(axes[0],))
assert sub_dcn.size == 2, sub_dcn.size
assert sub_dcn.rank == proc_id, sub_dcn.rank
assert sub_dcn.global_index == 4 * proc_id, sub_dcn.global_index
# roots are validated in the DENSE space, at the size boundary
try:
    sub_dcn.bcast_data({"w": jnp.ones(1)}, root=2)
    raise AssertionError("root=2 must be rejected on a size-2 communicator")
except ValueError:
    pass

# ---- full DP training run: grads allreduced ACROSS PROCESSES ------------
rng = np.random.RandomState(0)   # same on both procs: global dataset
x_all = rng.rand(64).astype(np.float32) * 2 - 1
y_all = 3.0 * x_all + 1.0
# each process feeds its local quarter-shards of the global batch
sharding = NamedSharding(comm.mesh, P(axes))
def to_global(a):
    lo = proc_id * 32
    return jax.make_array_from_process_local_data(
        sharding, a[lo:lo + 32], (64,))

def local_step(params, x, y):
    def loss_fn(p):
        pred = p["w"] * x + p["b"]
        return jnp.mean((pred - y) ** 2)
    loss, g = jax.value_and_grad(loss_fn)(params)
    g = comm.allreduce_grad(g, "mean")
    loss = jax.lax.pmean(loss, axes)
    return loss, g

step = jax.jit(shard_map(
    local_step, mesh=comm.mesh,
    in_specs=(P(), P(axes), P(axes)), out_specs=(P(), P())))

xg, yg = to_global(x_all), to_global(y_all)
loss = None
for i in range(120):
    loss, g = step(params, xg, yg)
    params = jax.tree_util.tree_map(lambda p, gg: p - 0.2 * gg, params, g)
    # sync EVERY iteration: this host has one core; letting collective-
    # bearing dispatches pile up starves the gloo/XLA rendezvous
    loss = float(jax.device_get(loss.addressable_shards[0].data))
w = float(params["w"].addressable_shards[0].data[0]) \
    if hasattr(params["w"], "addressable_shards") else float(params["w"][0])
b = float(params["b"].addressable_shards[0].data[0]) \
    if hasattr(params["b"], "addressable_shards") else float(params["b"][0])
assert abs(w - 3.0) < 1e-2 and abs(b - 1.0) < 1e-2, (w, b, loss)
assert loss < 1e-4, loss

# both processes must hold IDENTICAL parameters after synced training
from chainermn_tpu.comm.object_plane import ObjectPlane
got = ObjectPlane().allgather_obj((w, b))
assert got[0] == got[1], got

# ---- model parallel ACROSS PROCESSES: chain stages span the DCN seam ----
# (BASELINE config #5 multi-host: stage ranks 0,3,6 live on different
# process-local device groups, so the ppermute edges cross gloo)
import flax.linen as nn
from chainermn_tpu.links import MultiNodeChainList

class Part(nn.Module):
    feat: int
    @nn.compact
    def __call__(self, x):
        return jnp.tanh(nn.Dense(self.feat)(x))

chain = MultiNodeChainList(comm)
chain.add_link(Part(8), rank=0, rank_in=None, rank_out=3)
chain.add_link(Part(6), rank=3, rank_in=0, rank_out=6)
chain.add_link(Part(4), rank=6, rank_in=3, rank_out=None)

xin = np.random.RandomState(1).randn(5, 3).astype(np.float32)
cparams = chain.init(jax.random.PRNGKey(0), jnp.asarray(xin))
out = jax.jit(shard_map(
    lambda x: chain.apply(cparams, x), mesh=comm.mesh,
    in_specs=(P(),), out_specs=P()))(jnp.asarray(xin))
out = np.asarray(jax.device_get(out.addressable_shards[0].data))

h = jnp.asarray(xin)
for feat, p in zip([8, 6, 4], cparams):
    h = Part(feat).apply(p, h)
np.testing.assert_allclose(out, np.asarray(h), rtol=1e-5, atol=1e-6)

print(f"WORKER{proc_id} OK w={w:.4f} b={b:.4f}", flush=True)
"""




@pytest.mark.timeout(180)
def test_two_process_data_parallel_training(tmp_path):
    procs, outs = run_workers(
        _WORKER, tmp_path, timeout=170,
        env_extra={"XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert_all_ok(procs, outs)
