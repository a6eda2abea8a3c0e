"""The ``auto`` strategy: a bytes/hop-latency cost model picks flat vs
hierarchical vs quantized per bucket.

The model is the standard alpha-beta form per tier (latency ``alpha`` +
bytes/bandwidth ``beta``), with ring-allreduce byte counts
(``2·b·(k-1)/k`` per rank over a k-ring). Defaults are v5e-flavored
order-of-magnitude numbers (ICI ~100 GB/s per link / ~1 µs, DCN
~25 GB/s per host / ~100 µs — docs/scaling_model.md); the point is the
*crossover structure*, not the absolute numbers:

* tiny buckets are launch-latency bound → ``flat`` (one collective);
* large buckets on a multi-tier mesh → ``hierarchical`` (the inter tier
  carries ``1/intra`` of the bytes);
* with ``lossy=True``, very large buckets → ``quantized`` bf16 (half
  the wire bytes; OFF by default — a strategy named "auto" must not
  silently change numerics).

Override with measurement (:func:`measure_strategies`): on TPU it times
real compiled reductions per size and the picker interpolates the
table; off TPU it returns ``{}`` untimed — on a CPU host-platform mesh
every "collective" is a memcpy and the numbers would be fiction (the
``ops/autotune.py`` honest-null convention; BASELINE.md records the
null). Pass ``db=`` to persist a non-empty sweep into the per-topology
profile DB (:mod:`chainermn_tpu.tuning.profile_db`) so one on-TPU run
permanently improves off-TPU tuning for that machine shape;
``AutoReducer(profile=...)`` loads it back.

The intra/inter split itself is no longer hard-coded here: cost
estimation goes through the explicit multi-tier
:class:`chainermn_tpu.tuning.topology.Topology` (for two tiers the
numbers are identical to the original :class:`CostModel` formulas,
which remain as the parameter bag and the documented reference).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from chainermn_tpu.collectives.base import (
    GradReducer,
    group_leaves_for_buckets,
    register_reducer,
)
from chainermn_tpu.collectives.hierarchical import HierTopology
from chainermn_tpu.collectives.quantized import (
    quantize_allreduce,
    quantized_wire_bytes,
    wire_ratio,
)
from chainermn_tpu.utils import on_tpu


@dataclasses.dataclass
class CostModel:
    """Per-tier alpha-beta parameters, microseconds and GB/s.

    Kept as the two-tier parameter bag (and reference formulas);
    :meth:`as_topology` lifts it into the general multi-tier
    :class:`~chainermn_tpu.tuning.topology.Topology` the estimators
    now run on."""

    ici_latency_us: float = 1.0
    ici_bw_gbps: float = 100.0
    dcn_latency_us: float = 100.0
    dcn_bw_gbps: float = 25.0
    quant_overhead_us: float = 2.0  # quantize/dequantize kernels

    @staticmethod
    def _xfer_us(nbytes: float, bw_gbps: float) -> float:
        return nbytes / (bw_gbps * 1e3)  # 1 GB/s == 1e3 bytes/us

    def estimate_us(self, strategy: str, nbytes: int,
                    topo: HierTopology,
                    wire_format: str = "bf16") -> float:
        """Modeled time for ONE reduction of ``nbytes`` payload."""
        n, intra, inter = topo.n, topo.intra, topo.inter
        ring = lambda b, k: 2.0 * b * (k - 1) / max(k, 1)
        slow_lat = self.dcn_latency_us if inter > 1 else self.ici_latency_us
        slow_bw = self.dcn_bw_gbps if inter > 1 else self.ici_bw_gbps
        if strategy == "flat":
            # one allreduce whose ring crosses the slowest tier
            return slow_lat + self._xfer_us(ring(nbytes, n), slow_bw)
        if strategy == "hierarchical":
            t = 2 * self.ici_latency_us + self._xfer_us(
                ring(nbytes, intra), self.ici_bw_gbps)  # rs + ag, ICI
            if inter > 1:
                t += self.dcn_latency_us + self._xfer_us(
                    ring(nbytes / intra, inter), self.dcn_bw_gbps)
            return t
        if strategy == "quantized":
            # beta scales with the ACTUAL wire width (values + block
            # scales) — pricing every format at bf16 meant 'auto' could
            # never rationally pick the int8/int4 wires
            wire = nbytes * wire_ratio(wire_format)
            return (slow_lat + self.quant_overhead_us
                    + self._xfer_us(ring(wire, n), slow_bw))
        raise ValueError(f"unknown strategy {strategy!r}")

    def as_topology(self, comm, intra: Optional[int] = None):
        """This parameter set as an explicit multi-tier
        :class:`~chainermn_tpu.tuning.topology.Topology` over the
        communicator's mesh (bitwise-same estimates for two tiers)."""
        from chainermn_tpu.tuning.topology import Topology

        return Topology.from_comm(
            comm, intra=intra,
            ici_latency_us=self.ici_latency_us,
            ici_bw_gbps=self.ici_bw_gbps,
            dcn_latency_us=self.dcn_latency_us,
            dcn_bw_gbps=self.dcn_bw_gbps,
            quant_overhead_us=self.quant_overhead_us)


_CACHE: Dict[tuple, Dict[Tuple[str, int], float]] = {}


def _persist_measured(db, comm, intra, table) -> None:
    """Write a non-empty measured sweep into the profile DB under this
    mesh's topology fingerprint. ``db`` is a ProfileDB, a path, or
    ``True`` for the default DB location."""
    from chainermn_tpu.tuning.profile_db import ProfileDB
    from chainermn_tpu.tuning.topology import Topology

    pdb = db if isinstance(db, ProfileDB) else ProfileDB(
        db if isinstance(db, str) else None)
    pdb.put_measured(Topology.from_comm(comm, intra=intra), table)
    pdb.save()


def measure_strategies(
    comm,
    sizes: Sequence[int] = (1 << 16, 1 << 20, 1 << 22, 1 << 24),
    strategies: Sequence[str] = ("flat", "hierarchical", "quantized"),
    steps: int = 10,
    intra: Optional[int] = None,
    db=None,
) -> Dict[Tuple[str, int], float]:
    """Measured sweep: {(strategy, payload_bytes): microseconds}.

    Times real compiled reductions on the communicator's mesh. Memoized
    per (mesh shape, sizes, strategies). Off TPU this returns ``{}``
    UNTIMED — host-platform "collectives" are memcpys and any number
    would mislead the picker (honest-null convention, BASELINE.md).
    Feed the result to ``AutoReducer(measured=...)``.

    ``db`` (a :class:`~chainermn_tpu.tuning.profile_db.ProfileDB`, a
    path, or ``True`` for the default location) persists a NON-EMPTY
    sweep under this mesh's topology fingerprint — the results used to
    be computed and thrown away; now one on-TPU run feeds every later
    off-TPU ``AutoReducer(profile=...)`` / ``tools/schedtune.py`` run
    on that machine shape. The off-TPU ``{}`` null is never written.
    """
    key = (tuple(comm.mesh.devices.shape), tuple(comm.axis_names),
           tuple(sizes), tuple(strategies), intra)
    if key in _CACHE:
        if db is not None and _CACHE[key]:
            _persist_measured(db, comm, intra, _CACHE[key])
        return _CACHE[key]
    if not on_tpu():
        _CACHE[key] = {}  # not on a chip: nothing measured, nothing stored
        return {}
    import time

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    topo = HierTopology(comm, intra=intra)
    axes = comm.axis_names
    ax = axes if len(axes) > 1 else axes[0]
    out: Dict[Tuple[str, int], float] = {}
    for nbytes in sizes:
        nelem = max(1, nbytes // 4)
        x = jnp.ones((comm.size, nelem), jnp.float32)
        kernels = {
            "flat": lambda v: lax.psum(v, axes),
            "hierarchical": lambda v: topo.allreduce(v),
            "quantized": lambda v: quantize_allreduce(v, axes, "bf16")[0],
        }
        for s in strategies:
            f = jax.jit(shard_map(
                lambda v: kernels[s](v[0])[None], mesh=comm.mesh,
                in_specs=P(ax), out_specs=P(ax)))
            f(x).block_until_ready()  # compile
            t0 = time.perf_counter()
            for _ in range(steps):
                r = f(x)
            r.block_until_ready()
            out[(s, nbytes)] = (time.perf_counter() - t0) / steps * 1e6
    _CACHE[key] = out
    if db is not None and out:
        _persist_measured(db, comm, intra, out)
    return out


class AutoReducer(GradReducer):
    """Cost-model-driven per-bucket strategy choice (see module doc).

    Args (beyond the base): ``cost`` — a :class:`CostModel`;
    ``measured`` — a sweep table from :func:`measure_strategies`
    overriding the model where it has data; ``lossy`` — allow the
    quantized (bf16, no error feedback — this strategy is stateless)
    candidate; ``intra`` — fast-tier width, as in
    :class:`~chainermn_tpu.collectives.hierarchical.HierarchicalReducer`;
    ``topology`` — an explicit
    :class:`~chainermn_tpu.tuning.topology.Topology` for cost
    estimation (default: lifted from ``comm``/``cost``/``intra``);
    ``profile`` — a :class:`~chainermn_tpu.tuning.profile_db.ProfileDB`
    (or path, or ``True`` for the default location) whose persisted
    ``measure_strategies`` sweep for this topology fingerprint seeds
    ``measured`` (an explicit ``measured=`` entry wins per key);
    ``wire_format`` — the wire the quantized candidate uses AND is
    priced at (default ``'bf16'``, the historical behavior; the block
    formats make the quantized candidate ~4–8x cheaper on beta, so the
    cost model can actually choose it). ``wire_format='f32'`` disables
    the lossy candidate outright (an uncompressed "quantized" wire is
    the flat strategy). Implies nothing unless ``lossy=True`` — a
    strategy named "auto" must not silently change numerics.
    """

    name = "auto"
    wire_formats = ("f32", "bf16", "int8", "int8-block", "int4-block")

    def __init__(self, comm, op: str = "mean",
                 bucket_bytes: Optional[int] = None,
                 intra: Optional[int] = None,
                 cost: Optional[CostModel] = None,
                 measured: Optional[Dict[Tuple[str, int], float]] = None,
                 lossy: bool = False,
                 bucket_order: str = "emission",
                 topology=None,
                 profile=None,
                 wire_format: Optional[str] = None):
        super().__init__(comm, op, bucket_bytes, bucket_order)
        if wire_format is not None and wire_format not in self.wire_formats:
            raise ValueError(
                f"unknown wire_format {wire_format!r}; expected one of "
                f"{self.wire_formats}")
        if wire_format == "f32":
            lossy = False
        self.wire_format = (wire_format if wire_format not in (None, "f32")
                            else "bf16")
        self.topology = HierTopology(comm, intra=intra)
        self.cost = cost or CostModel()
        #: multi-tier cost-side description (the collective kernels
        #: still run on the two-tier HierTopology above)
        self.topo_desc = (topology if topology is not None
                          else self.cost.as_topology(comm, intra=intra))
        self.measured = dict(measured or {})
        if profile is not None:
            from chainermn_tpu.tuning.profile_db import ProfileDB

            pdb = profile if isinstance(profile, ProfileDB) else ProfileDB(
                profile if isinstance(profile, str) else None)
            persisted = pdb.measured_for(self.topo_desc)
            self.measured = {**persisted, **self.measured}
        self.lossy = lossy

    def _estimate(self, strategy: str, nbytes: int) -> float:
        if self.measured:
            pts = [(abs(sz - nbytes), us) for (s, sz), us
                   in self.measured.items() if s == strategy]
            if pts:  # nearest measured size wins over the model
                return min(pts)[1]
        return self.topo_desc.estimate_us(strategy, nbytes,
                                          wire_format=self.wire_format)

    def choose(self, nbytes: int) -> str:
        cands = ["flat", "hierarchical"] + (
            ["quantized"] if self.lossy else [])
        # stable tie-break: flat first (fewest launches, exact)
        return min(cands, key=lambda s: (self._estimate(s, nbytes),
                                         cands.index(s)))

    def reduce(self, grads, state=()):
        comm = self.comm
        axes = comm.axis_names
        n = comm.size
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        out = [None] * len(leaves)
        passthrough, groups = group_leaves_for_buckets(
            leaves, axes, self.bucket_bytes, order=self.bucket_order)
        for i in passthrough:
            out[i] = leaves[i] / n if self.op == "mean" else leaves[i]
        for (va, cdt), buckets in groups.items():
            full_tier = tuple(va) == tuple(axes)
            lossy_ok = self.lossy and jnp.issubdtype(cdt, jnp.floating)
            for bucket in buckets:
                flat = jnp.concatenate(
                    [leaves[i].astype(cdt).ravel() for i in bucket])
                nbytes = flat.size * cdt.itemsize
                algo = self.choose(nbytes)
                if algo == "hierarchical" and full_tier:
                    red = self.topology.allreduce(flat)
                elif algo == "quantized" and lossy_ok:
                    red = quantize_allreduce(flat, va, self.wire_format)[0]
                else:
                    red = lax.psum(flat, va)
                off = 0
                for i in bucket:
                    l = leaves[i]
                    piece = red[off:off + l.size].reshape(l.shape).astype(
                        l.dtype)
                    off += l.size
                    out[i] = piece / n if self.op == "mean" else piece
        return jax.tree_util.tree_unflatten(treedef, out), state

    def reduce_scatter_flat(self, g, ax: str, n: int):
        nbytes = g.size * g.dtype.itemsize
        if self.choose(nbytes) == "hierarchical":
            return self.topology.reduce_scatter(g, ax) / n
        return lax.psum_scatter(g, ax, tiled=True) / n

    def plan(self, tree):
        rows = super().plan(tree)
        for b in rows:
            algo = self.choose(b["bytes"])
            b["algorithm"] = f"auto:{algo}"
            b["wire_bytes"] = (
                quantized_wire_bytes(b["bytes"], self.wire_format)
                if algo == "quantized" else b["bytes"])
            b["est_us"] = round(self._estimate(algo, b["bytes"]), 2)
        return rows


register_reducer("auto", AutoReducer)
