"""Multi-slice topology: 2 processes × 4 local devices (dcn, ici).

Every other multiprocess test runs 1 device per process, degenerating the
(dcn, ici) mesh to (2, 1). Here each worker forces 4 virtual CPU devices,
so the hierarchical factory builds the REAL two-level shape — 2 slices × 4
chips — and the round's multi-slice machinery runs on it end to end:
bf16 bucketed allreduce_grad training across BOTH axes, eager P2P between
slice-canonical ranks, and payload-shipping scatter_dataset.
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
assert jax.local_device_count() == 4 and jax.device_count() == 8
sys.path.insert(0, os.environ["REPO_ROOT"])

import numpy as np
import jax.numpy as jnp
import chainermn_tpu

from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

comm = chainermn_tpu.create_communicator(
    "hierarchical", allreduce_grad_dtype=jnp.bfloat16,
    dcn_bucket_bytes=64)
assert comm.mesh.devices.shape == (2, 4), comm.mesh.devices.shape
assert comm.size == 8 and comm.inter_size == 2 and comm.intra_size == 4

# ---- 1. bf16 bucketed DP training across both mesh axes ----------------
params = comm.bcast_data({"w": np.zeros((2,), np.float32)})
lr = 0.2

def local_step(params, x, y):
    def loss(p):
        return jnp.mean((x * p["w"][0] + p["w"][1] - y) ** 2)
    g = jax.grad(loss)(params)
    g = comm.allreduce_grad(g, "mean")
    return jax.tree_util.tree_map(lambda p, gg: p - lr * gg, params, g)

xspec = P(("dcn", "ici"))
step = jax.jit(shard_map(
    local_step, mesh=comm.mesh, in_specs=(P(), xspec, xspec),
    out_specs=P()))
rng = np.random.RandomState(0)
x = rng.randn(64).astype(np.float32)
y = (3.0 * x + 1.0).astype(np.float32)
dsh = NamedSharding(comm.mesh, xspec)
xg = jax.make_array_from_process_local_data(dsh, x[proc_id*32:(proc_id+1)*32])
yg = jax.make_array_from_process_local_data(dsh, y[proc_id*32:(proc_id+1)*32])
for _ in range(120):
    params = step(params, xg, yg)
    # 1-core box: sync every step or the rendezvous aborts under load
    jax.block_until_ready(params)
w = np.asarray(params["w"].addressable_shards[0].data)
np.testing.assert_allclose(w, [3.0, 1.0], atol=5e-2)

# ---- 2. eager P2P between slice-canonical ranks ------------------------
# ranks 0..3 live on process 0, 4..7 on process 1; canonical ranks 0 and 4
me, peer = (0, 4) if proc_id == 0 else (4, 0)
assert comm.rank == me
payload = np.full((3, 3), float(proc_id + 1), np.float32)
comm.send(payload, dest=peer, tag=1)
got = comm.recv(src=peer, tag=1)
np.testing.assert_allclose(np.asarray(got),
                           np.full((3, 3), float(2 - proc_id)))
# non-canonical rank targets ride their own (tag, src, dest) channel
# (round-3 upgrade; the dedicated matrix lives in
# test_multiprocess_eager_p2p.py::test_two_process_noncanonical_rank_p2p)
nc = 5 if proc_id == 0 else 1
comm.send(payload * 3.0, dest=nc, tag=2)
got_nc = comm.recv(src=peer, tag=2, as_rank=me + 1)
np.testing.assert_allclose(np.asarray(got_nc),
                           np.full((3, 3), 3.0 * float(2 - proc_id)))

# ---- 3. payload scatter across the slices ------------------------------
from chainermn_tpu.datasets import ListDataset, scatter_dataset
data = [("sample", i, np.arange(i % 4 + 1)) for i in range(12)] \
    if proc_id == 0 else None
shard = scatter_dataset(data, comm, shuffle=True, seed=2,
                        shared_storage=False)
assert isinstance(shard, ListDataset) and len(shard) == 6
ids = comm.allgather_obj([shard[i][1] for i in range(len(shard))])
assert sorted(i for lst in ids for i in lst) == sorted(list(range(12))), ids

print(f"WORKER{proc_id} OK", flush=True)
"""


@pytest.mark.timeout(240)
def test_two_slice_topology(tmp_path):
    procs, outs = run_workers(
        _WORKER, tmp_path, timeout=230,
        env_extra={"XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert_all_ok(procs, outs)
