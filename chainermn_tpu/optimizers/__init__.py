"""Multi-node optimizer wrapper.

Reference: chainermn/optimizers/__init__.py (SURVEY.md §2.5; mount empty —
module path citation). ``create_multi_node_optimizer(opt, comm)`` wraps any
Chainer optimizer so ``update()`` runs ``communicator.allreduce_grad(model)``
between backward and the inner update, and ``setup()`` broadcasts initial
parameters. ``double_buffering=True`` overlaps step t-1's communication with
step t's compute at the cost of one-step-stale gradients
(``_DoubleBufferingOptimizer``).

TPU-native form: the wrapper is an :class:`optax.GradientTransformation`
whose ``update`` inserts the gradient all-reduce *inside the compiled step* —
XLA's latency-hiding scheduler then overlaps the collective with adjacent
compute automatically, which is what the reference's double-buffering thread
approximated by hand. The stale-gradient mode is still available as an
explicit opt-in (same accuracy caveats as the reference).
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import optax
from jax.sharding import NamedSharding, PartitionSpec

from chainermn_tpu.comm.base import CommunicatorBase
from chainermn_tpu.optimizers.zero import (  # noqa: F401
    fsdp_gather_params,
    fsdp_layout_manifest,
    fsdp_scan_apply,
    fsdp_shardings,
    fsdp_stack_shardings,
    make_fsdp_train_step,
    make_zero1_train_step,
    make_zero2_train_step,
    zero1_params,
    zero_layout_manifest,
)


class _DoubleBufferState(NamedTuple):
    inner: Any
    prev_grads: Any  # step t-1's reduced grads (applied this step)
    is_first: Any    # scalar flag; first step applies zeros


class _ReducerWrappedState(NamedTuple):
    """Optimizer state carrying explicit reducer state (error-feedback
    residuals) alongside the inner optimizer's. Only STATEFUL reducers
    introduce this wrapper — the default/stateless paths keep the inner
    state layout byte-for-byte, so existing checkpoints stay valid.

    Inside the compiled step ``reducer`` holds the per-rank view; at the
    driver level it holds the per-rank states stacked on a leading
    ``comm.size`` axis (``make_data_parallel_train_step`` shards and
    (un)stacks it around the update — the residuals are genuinely
    per-rank data, unlike the replicated inner state)."""

    inner: Any
    reducer: Any


class MultiNodeOptimizer(NamedTuple):
    """Duck-types :class:`optax.GradientTransformation` (same
    ``init``/``update`` fields — optax composes by duck typing) while
    exposing the bound :class:`~chainermn_tpu.collectives.GradReducer`
    so step factories can shard its state, and the tuned
    :class:`~chainermn_tpu.tuning.profile_db.SchedulePlan` (when
    ``tune=`` chose the knobs) so reports/benches can log what the
    tuner picked."""

    init: Any
    update: Any
    grad_reducer: Any = None
    plan: Any = None


def create_multi_node_optimizer(
    actual_optimizer: optax.GradientTransformation,
    communicator: CommunicatorBase,
    double_buffering: bool = False,
    op: str = "mean",
    grad_reducer: Any = None,
    tune: Any = None,
    model_key: Optional[str] = None,
    wire_format: Optional[str] = None,
    topology: Any = None,
) -> optax.GradientTransformation:
    """Wrap an optax optimizer with the gradient all-reduce.

    Use exactly like the inner optimizer::

        opt = create_multi_node_optimizer(optax.adam(1e-3), comm)
        state = opt.init(params)              # inside or outside jit
        updates, state = opt.update(grads, state, params)  # inside the step

    ``update`` must run inside the jitted (shard_map/pjit) training step so
    the all-reduce compiles into the program. ``allreduce_grad`` is
    varying-axis-aware (see XlaCommunicator.allreduce_grad), so this is safe
    both when autodiff already summed the gradients and when it did not.

    ``grad_reducer`` selects the reduction strategy (the reference's
    communicator-zoo axis, docs/collectives.md): ``None`` (default) and
    ``'flat'`` are today's psum — bit-identical; ``'hierarchical'``,
    ``'quantized'``, ``'auto'``, or a constructed
    :class:`~chainermn_tpu.collectives.GradReducer` instance select the
    two-level, error-feedback-quantized, or cost-model strategies. A
    STATEFUL reducer (quantized with error feedback) changes the state
    layout to :class:`_ReducerWrappedState` and must be initialized at
    the driver level (``opt.init(params)`` outside jit) — the residuals
    are per-rank and ride the optimizer state through the step and
    through checkpoints.

    ``tune`` injects a schedtune profile (docs/tuning.md): a
    :class:`~chainermn_tpu.tuning.profile_db.SchedulePlan`, a
    :class:`~chainermn_tpu.tuning.profile_db.ProfileDB`, a DB path, or
    ``True`` for the default DB location. The stored plan's strategy /
    ``bucket_bytes`` / ``bucket_order`` build the reducer (unless an
    explicit ``grad_reducer`` was also passed, which wins) and its
    ``double_buffering`` flag ORs into ``double_buffering``.
    ``model_key`` selects among plans stored for several model shapes
    (see ``tuning.model_key_for``; ``None`` accepts a sole/default
    plan). A plan whose topology fingerprint does not match this
    communicator's mesh raises ``ValueError`` — the wrong-machine
    profile bug dlint DL107 flags statically.

    ``wire_format`` selects the compressed wire
    (docs/collectives.md#quantized-wire-formats): ``'bf16' | 'int8' |
    'int8-block' | 'int4-block'`` are forwarded to the reducer being
    built (from a name or a tuned plan); an explicit value overrides a
    tuned plan's recorded format. ``'f32'``/``None`` keep the strategy's
    own default. Refused (ValueError) when the resolved strategy cannot
    compress — same rule as ``make_grad_reducer``.

    ``topology`` supplies the explicit
    :class:`~chainermn_tpu.tuning.topology.Topology` the ``tune`` plan
    was produced for, instead of the ``Topology.from_comm`` inference.
    Required when the plan was tuned for a tier decomposition the mesh
    does not expose (e.g. a factored ``(inter, intra)`` view of a
    single-axis mesh — synthesized programs carry their ``tier_sizes``
    and are rebuilt against this decomposition). Its total rank count
    must match the communicator.
    """
    from chainermn_tpu.collectives import make_grad_reducer

    plan = None
    if tune is not None:
        from chainermn_tpu.tuning import ProfileDB, SchedulePlan, Topology

        if topology is not None:
            if topology.n != communicator.size:
                raise ValueError(
                    f"explicit topology has {topology.n} ranks but the "
                    f"communicator has {communicator.size}")
            topo = topology
        else:
            topo = Topology.from_comm(communicator)
        if isinstance(tune, SchedulePlan):
            plan = tune
        else:
            db = tune if isinstance(tune, ProfileDB) else ProfileDB(
                tune if isinstance(tune, str) else None)
            plan = db.plan_for(topo, model_key)
            if plan is None:
                raise ValueError(
                    f"no tuned schedule for topology "
                    f"{topo.fingerprint()!r} (model_key={model_key!r}) "
                    f"in profile DB {db.path!r}; run tools/schedtune.py "
                    "on this machine first")
        if plan.fingerprint and plan.fingerprint != topo.fingerprint():
            raise ValueError(
                f"stale schedule profile: plan was tuned for "
                f"{plan.fingerprint!r} but this mesh is "
                f"{topo.fingerprint()!r} — wrong-machine profiles "
                "silently mis-tune (dlint DL107); re-run "
                "tools/schedtune.py here")
        if grad_reducer is None:
            wf = wire_format or getattr(plan, "wire_format", None)
            extra = {}
            if getattr(plan, "program", None) is not None:
                extra["program"] = plan.program  # 'synth' plans only
            grad_reducer = make_grad_reducer(
                plan.strategy, communicator, op=op,
                bucket_bytes=plan.bucket_bytes,
                bucket_order=plan.bucket_order,
                wire_format=wf, **extra)
        double_buffering = bool(double_buffering or plan.double_buffering)

    if isinstance(grad_reducer, str):
        reducer = make_grad_reducer(grad_reducer, communicator, op=op,
                                    wire_format=wire_format)
    else:
        if wire_format not in (None, "f32") and grad_reducer is None:
            raise ValueError(
                f"wire_format={wire_format!r} needs a compressing "
                "grad_reducer ('quantized' or 'auto'); the default flat "
                "psum carries f32")
        reducer = make_grad_reducer(grad_reducer, communicator, op=op)
    stateful = bool(reducer is not None and reducer.stateful)

    if reducer is None:
        def reduce_grads(grads, rstate):
            return communicator.allreduce_grad(grads, op), rstate
    else:
        reduce_grads = reducer.reduce

    def reduce_fn(grads, rstate):
        # the collective AND its scaling (op='mean'), named for the trace
        with jax.named_scope("grad_reduce"):
            return reduce_grads(grads, rstate)

    import jax.numpy as jnp

    if not double_buffering:

        def inner_init(params):
            return actual_optimizer.init(params)

        def inner_update(grads, state, params=None, **extra):
            # state here is the INNER state; grads are already reduced
            return actual_optimizer.update(grads, state, params, **extra)

    else:

        def inner_init(params):
            zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
            return _DoubleBufferState(
                inner=actual_optimizer.init(params),
                prev_grads=zeros,
                is_first=jnp.array(True),
            )

        def inner_update(reduced, state, params=None, **extra):
            # Reference semantics (_DoubleBufferingOptimizer): apply step
            # t-1's reduced grads while step t's reduction is in flight;
            # first step applies nothing. In one compiled program "in
            # flight" is the XLA scheduler's overlap; the visible
            # semantic is the one-step lag.
            apply = jax.tree_util.tree_map(
                lambda p: jnp.where(state.is_first, jnp.zeros_like(p), p),
                state.prev_grads,
            )
            updates, inner = actual_optimizer.update(
                apply, state.inner, params, **extra)
            return updates, _DoubleBufferState(
                inner=inner, prev_grads=reduced, is_first=jnp.array(False)
            )

    def on_mesh(state):
        """A state born at the driver level lives where the step will
        leave it. optax creates leaves no parameter places (adam's step
        counter): uncommitted, on one device. The step returns them on the
        mesh, the array's type changes with that, and jit would trace and
        compile the step a second time for its second call. Leaves traced
        inside jit/shard_map pass through."""
        rep = NamedSharding(communicator.mesh, PartitionSpec())

        def place(leaf):
            if not isinstance(leaf, jax.Array) or isinstance(
                    leaf, jax.core.Tracer):
                return leaf
            sh = leaf.sharding
            if isinstance(sh, NamedSharding) and sh.mesh == rep.mesh:
                return leaf
            return jax.device_put(leaf, rep)

        return jax.tree_util.tree_map(place, state)

    if not stateful:

        def init(params):
            return on_mesh(inner_init(params))

        def update(grads, state, params=None, **extra):
            grads, _ = reduce_fn(grads, ())
            return inner_update(grads, state, params, **extra)

        if reducer is None:
            return optax.GradientTransformation(init, update)
        return MultiNodeOptimizer(init, update, reducer, plan)

    def init_st(params):
        return _ReducerWrappedState(
            inner=on_mesh(inner_init(params)),
            reducer=reducer.init_global(params),
        )

    def update_st(grads, state, params=None, **extra):
        grads, rstate = reduce_fn(grads, state.reducer)
        updates, inner = inner_update(grads, state.inner, params, **extra)
        return updates, _ReducerWrappedState(inner=inner, reducer=rstate)

    return MultiNodeOptimizer(init_st, update_st, reducer, plan)
