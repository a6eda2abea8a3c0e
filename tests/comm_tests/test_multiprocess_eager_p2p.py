"""Eager host-level P2P + hierarchical bf16 grad path (VERDICT r1 #7/#9).

Reference scripts call blocking ``comm.send(array, dest)`` /
``comm.recv(src)`` mid-script on concrete arrays
(mpi_communicator_base.py semantics, SURVEY.md §2.1). Two real
``jax.distributed`` processes exercise that surface — arrays and pytrees,
both directions, tag-disambiguated — plus an end-to-end training run under
``create_communicator('hierarchical', allreduce_grad_dtype=bf16,
dcn_bucket_bytes=...)`` on the (dcn, ici) mesh: the bf16 comm-dtype
gradient path crossing BOTH mesh axes with bucketing live.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from mp_harness import assert_all_ok, run_workers

_P2P_WORKER = r"""
import os, sys
proc_id = int(sys.argv[1])
port = sys.argv[2]
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
jax.distributed.initialize(
    coordinator_address=f"127.0.0.1:{port}", num_processes=2,
    process_id=proc_id)
sys.path.insert(0, os.environ["REPO_ROOT"])

import numpy as np
import chainermn_tpu

comm = chainermn_tpu.create_communicator("xla")
assert comm.size == 2 and comm.inter_size == 2
peer = 1 - comm.rank

# reference-shaped eager exchange: rank 0 sends, rank 1 transforms, returns
x = np.arange(6, dtype=np.float32).reshape(2, 3) * (comm.rank + 1)
if comm.rank == 0:
    comm.send(x, dest=peer)
    back = comm.recv(src=peer)
    np.testing.assert_allclose(np.asarray(back), x * 10.0)
else:
    got = comm.recv(src=peer)
    comm.send(np.asarray(got) * 10.0, dest=peer)

# pytrees + tags: two outstanding messages disambiguated by tag
tree = {"a": np.ones((4,), np.float32) * comm.rank,
        "b": [np.int32(comm.rank), np.full((2, 2), 7.0, np.float32)]}
comm.send(tree, dest=peer, tag=5)
comm.send(np.float32(comm.rank + 100), dest=peer, tag=6)
t = comm.recv(src=peer, tag=5)
s = comm.recv(src=peer, tag=6)
np.testing.assert_allclose(np.asarray(t["a"]), np.ones(4) * peer)
assert int(t["b"][0]) == peer
assert float(s) == peer + 100

# received arrays are device-committed (usable in jitted compute)
y = jax.jit(lambda v: v * 2)(t["a"])
np.testing.assert_allclose(np.asarray(y), np.ones(4) * peer * 2)

# same-process target still errors helpfully
try:
    comm.send(x, dest=comm.rank)
except ValueError:
    pass
else:
    raise AssertionError("same-process eager send should raise")

print(f"WORKER{proc_id} OK", flush=True)
"""

_HIER_WORKER = r"""
import os, sys
proc_id = int(sys.argv[1])
port = sys.argv[2]
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
jax.distributed.initialize(
    coordinator_address=f"127.0.0.1:{port}", num_processes=2,
    process_id=proc_id)
sys.path.insert(0, os.environ["REPO_ROOT"])

import numpy as np
import jax.numpy as jnp
import chainermn_tpu

from jax import shard_map
from jax.sharding import PartitionSpec as P

comm = chainermn_tpu.create_communicator(
    "hierarchical", allreduce_grad_dtype=jnp.bfloat16,
    dcn_bucket_bytes=32)
assert comm.mesh.axis_names == ("dcn", "ici")
assert comm.axis_names == ("dcn", "ici")

params = comm.bcast_data({"w": np.zeros((2,), np.float32),
                          "v": np.zeros((3,), np.float32)})
lr = 0.2

def local_step(params, x, y):
    def loss(p):
        return jnp.mean((x * p["w"][0] + p["w"][1]
                         + 0.0 * jnp.sum(p["v"]) - y) ** 2)
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
from jax.sharding import NamedSharding
dsh = NamedSharding(comm.mesh, xspec)
xg = jax.make_array_from_process_local_data(dsh, x[proc_id*32:(proc_id+1)*32])
yg = jax.make_array_from_process_local_data(dsh, y[proc_id*32:(proc_id+1)*32])
for _ in range(150):
    params = step(params, xg, yg)
    jax.block_until_ready(params)  # per-iter sync (conftest 1-core rule)
w = np.asarray(jax.device_get(
    jax.tree_util.tree_map(lambda l: l, params)["w"]))
np.testing.assert_allclose(w, [3.0, 1.0], atol=5e-2)

print(f"WORKER{proc_id} OK", flush=True)
"""


@pytest.mark.timeout(150)
def test_two_process_eager_p2p(tmp_path):
    procs, outs = run_workers(_P2P_WORKER, tmp_path, timeout=140)
    assert_all_ok(procs, outs)


@pytest.mark.timeout(150)
def test_hierarchical_bf16_bucketed_training(tmp_path):
    procs, outs = run_workers(_HIER_WORKER, tmp_path, timeout=140)
    assert_all_ok(procs, outs)


_NCA_WORKER = r"""
import os, sys
proc_id = int(sys.argv[1])
port = sys.argv[2]
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
jax.distributed.initialize(
    coordinator_address=f"127.0.0.1:{port}", num_processes=2,
    process_id=proc_id)
sys.path.insert(0, os.environ["REPO_ROOT"])

import numpy as np
import jax.numpy as jnp
import chainermn_tpu

comm = chainermn_tpu.create_communicator(
    "non_cuda_aware", allreduce_grad_dtype=jnp.bfloat16)
assert comm.inter_size == 2 and comm.size == 2

# multi-process contract: each process stacks its LOCAL ranks (1 here)
local = np.asarray([[10.0 * (proc_id + 1), 1.0 + proc_id]], np.float32)
out = np.asarray(comm.allreduce(local, "sum"))
np.testing.assert_allclose(out, [30.0, 3.0])
out = np.asarray(comm.allreduce(local, "mean"))
np.testing.assert_allclose(out, [15.0, 1.5])
out = np.asarray(comm.allreduce(local, "max"))
np.testing.assert_allclose(out, [20.0, 2.0])

# comm-dtype grad path across processes, also host-staged
g = {"w": np.asarray([[1.0 + proc_id, 4.0]], np.float32)}
got = comm.allreduce_grad(g, "mean")
np.testing.assert_allclose(np.asarray(got["w"]), [1.5, 4.0], rtol=1e-2)
assert not comm._jit_cache  # never compiled a collective

# a full-rank-space stack is the single-controller form: rejected here
try:
    comm.allreduce(np.zeros((2, 3), np.float32), "sum")
except ValueError:
    pass
else:
    raise AssertionError("global stack should be rejected multi-process")

print(f"WORKER{proc_id} OK", flush=True)
"""


@pytest.mark.timeout(150)
def test_two_process_host_staged_allreduce(tmp_path):
    procs, outs = run_workers(_NCA_WORKER, tmp_path, timeout=140)
    assert_all_ok(procs, outs)


_NONCANON_WORKER = r"""
import os, sys
proc_id = int(sys.argv[1])
port = sys.argv[2]
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
jax.distributed.initialize(
    coordinator_address=f"127.0.0.1:{port}", num_processes=2,
    process_id=proc_id)
sys.path.insert(0, os.environ["REPO_ROOT"])

import numpy as np
import chainermn_tpu

comm = chainermn_tpu.create_communicator("xla")
assert comm.size == 8 and comm.inter_size == 2
# proc 0 hosts ranks 0-3 (canonical 0), proc 1 hosts 4-7 (canonical 4)
if proc_id == 0:
    assert comm.rank == 0
    # address two NON-CANONICAL ranks of the peer with the SAME tag:
    # separate per-rank-pair channels must never interleave
    comm.send(np.float32(60.0), dest=6, tag=3)
    comm.send(np.float32(50.0), dest=5, tag=3)
    # and send AS a non-canonical local rank
    comm.send(np.float32(20.0), dest=4, tag=4, as_rank=2)
    back = comm.recv(src=7, tag=9, as_rank=1)
    assert float(back) == 77.0, back
else:
    assert comm.rank == 4
    five = comm.recv(src=0, tag=3, as_rank=5)
    six = comm.recv(src=0, tag=3, as_rank=6)
    assert float(five) == 50.0 and float(six) == 60.0, (five, six)
    as2 = comm.recv(src=2, tag=4)
    assert float(as2) == 20.0, as2
    comm.send(np.float32(77.0), dest=1, tag=9, as_rank=7)
    # a rank this process does not host is rejected
    try:
        comm.recv(src=0, tag=0, as_rank=2)
    except ValueError:
        pass
    else:
        raise AssertionError("foreign as_rank should raise")

print(f"WORKER{proc_id} OK", flush=True)
"""


def test_two_process_noncanonical_rank_p2p(tmp_path):
    procs, outs = run_workers(
        _NONCANON_WORKER, tmp_path, timeout=140,
        env_extra={"XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert_all_ok(procs, outs)
