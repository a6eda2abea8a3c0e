"""Hierarchical reduction over a REAL process boundary.

Two `jax.distributed` processes x four virtual CPU devices = one global
8-device mesh where `comm.intra_size == 4` / `inter_size == 2` — so
`HierarchicalReducer`'s DEFAULT topology (intra = comm.intra_size)
factors exactly along the process boundary: the reduce-scatter and
all-gather stay intra-process, only the shrunk inter all-reduce crosses
gloo (the CPU stand-in for DCN). Parity vs flat psum and a short
converging DP run, both over the real multi-process mesh.
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
import optax

import chainermn_tpu
from chainermn_tpu.collectives import HierarchicalReducer, HierTopology
from chainermn_tpu.models import MLP
from chainermn_tpu.training.step import make_data_parallel_train_step

from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

comm = chainermn_tpu.create_communicator("xla")
assert comm.size == 8 and comm.intra_size == 4, (comm.size, comm.intra_size)
ax = comm.axis_names[0]

# -- the default topology factors along the process boundary --------------
topo = HierTopology(comm)
assert (topo.intra, topo.inter) == (4, 2), (topo.intra, topo.inter)

# -- bitwise parity vs flat psum on integer-valued floats -----------------
rs = np.random.RandomState(0)
x = rs.randint(-8, 8, size=(8, 513)).astype(np.float32)  # odd: pads
sh = NamedSharding(comm.mesh, P(ax))
xg = jax.make_array_from_process_local_data(sh, x[proc_id * 4:(proc_id + 1) * 4])

def reduce_with(kernel):
    f = jax.jit(shard_map(lambda v: kernel(v[0])[None], mesh=comm.mesh,
                          in_specs=P(ax), out_specs=P(ax)))
    out = f(xg)
    return np.stack([np.asarray(s.data) for s in out.addressable_shards])

flat = reduce_with(lambda v: lax.psum(v, ax))
hier = reduce_with(topo.allreduce)
np.testing.assert_array_equal(flat, hier)
np.testing.assert_array_equal(flat[0, 0], x.sum(axis=0))

# -- short DP training run with grad_reducer='hierarchical' ---------------
model = MLP(n_units=16, n_out=10)
params = model.init(jax.random.PRNGKey(0),
                    np.zeros((2, 28, 28), np.float32))["params"]
params = comm.bcast_data(params)
opt = chainermn_tpu.create_multi_node_optimizer(
    optax.adam(1e-2), comm, grad_reducer=HierarchicalReducer(comm))
state = (params, jax.jit(opt.init)(params))
step = make_data_parallel_train_step(model, opt, comm, donate=False)

drs = np.random.RandomState(1)
n = 16
bx = drs.rand(n, 28, 28).astype(np.float32)
by = drs.randint(0, 10, size=(n,)).astype(np.int32)
bxg = jax.make_array_from_process_local_data(
    sh, bx[proc_id * 8:(proc_id + 1) * 8])
byg = jax.make_array_from_process_local_data(
    sh, by[proc_id * 8:(proc_id + 1) * 8])

losses = []
for _ in range(5):
    state, m = step(state, bxg, byg)
    losses.append(float(m["main/loss"]))  # per-iteration sync
assert np.isfinite(losses).all(), losses
assert losses[-1] < losses[0], losses

print(f"WORKER{proc_id} OK", flush=True)
"""


@pytest.mark.timeout(180)
def test_hierarchical_reduction_across_processes(tmp_path):
    procs, outs = run_workers(_WORKER, tmp_path)
    assert_all_ok(procs, outs)
