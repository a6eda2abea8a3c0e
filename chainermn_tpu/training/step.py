"""Train/eval step factories: the framework's compiled hot path.

Reference hot loop (SURVEY.md §3.1): forward/backward, pack grads, NCCL
allreduce, unpack, optimizer update — four host-driven phases. Here the whole
iteration is ONE compiled XLA program over the mesh: loss/grad, gradient
all-reduce (vma-aware psum), optimizer update, and metric reduction, with
XLA overlapping the collective against adjacent compute (what the
reference's double-buffering thread did by hand).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from chainermn_tpu import tracing


def _tree_bytes(tree) -> int:
    return sum(getattr(leaf, "nbytes", 0)
               for leaf in jax.tree_util.tree_leaves(tree))


def _step_build(state=None):
    """The ``step.build`` lifecycle span (tracing.py) of a factory below:
    around the making of the jitted ``shard_map`` and, in
    ``init_expert_parallel_state``, the parameters' placement and
    ``jax.jit(optimizer.init)``. It carries ``param_bytes`` and
    ``opt_state_bytes`` where the factory has the state at hand."""
    if state is None:
        return tracing.lifecycle_span("step.build")
    return tracing.lifecycle_span(
        "step.build", param_bytes=_tree_bytes(state[0]),
        opt_state_bytes=_tree_bytes(state[1]))


def _accepts_train(model) -> bool:
    import inspect

    try:
        sig = inspect.signature(type(model).__call__)
    except (TypeError, ValueError):
        return False
    return "train" in sig.parameters


def classifier_loss(model, params, x, y, train: bool = True,
                    mutable=None, extra_vars=None, rngs=None):
    """Softmax cross-entropy + accuracy for an (x, y) classifier.

    The ``train`` flag is forwarded whenever the model's ``__call__``
    declares it (dropout/BN models), independent of whether mutable
    collections exist.
    """
    variables = {"params": params, **(extra_vars or {})}
    kwargs = {}
    if _accepts_train(model):
        kwargs["train"] = train
    if mutable and train:
        logits, new_vars = model.apply(variables, x, mutable=list(mutable),
                                       rngs=rngs, **kwargs)
    else:
        logits = model.apply(variables, x, rngs=rngs, **kwargs)
        new_vars = {}
    loss = optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()
    acc = jnp.mean((jnp.argmax(logits, -1) == y).astype(jnp.float32))
    return loss, (acc, new_vars)


def make_data_parallel_train_step(
    model,
    optimizer: optax.GradientTransformation,
    comm,
    loss_fn: Optional[Callable] = None,
    mutable: Optional[Tuple[str, ...]] = None,
    donate: bool = True,
    grad_accum: int = 1,
    remat: Any = False,
    with_rng: bool = False,
    scan_steps: int = 1,
):
    """Build the jitted data-parallel train step.

    ``state = (params, opt_state)`` or ``(params, opt_state, extra_vars)``
    when ``mutable`` names flax variable collections (e.g. BN
    ``('batch_stats',)`` — their new values are pmean-synced across replicas,
    the reference's MultiNodeBatchNormalization/AllreducePersistent
    semantics). The optimizer should already wrap the communicator
    (create_multi_node_optimizer); a plain optax optimizer also works when
    autodiff inserts the psum (default shard_map mode).

    ``with_rng=True`` changes the step signature to
    ``step(state, x, y, rng)`` and threads per-shard dropout keys into the
    loss (``rng`` is one PRNGKey; each shard folds in its mesh position, and
    each micro-batch its index, so masks decorrelate). Required for models
    with dropout — without it the loss runs rng-less and flax raises.

    ``scan_steps=K`` compiles K optimizer steps into ONE XLA program via
    ``lax.scan``: the step signature becomes ``step(state, xs, ys)`` where
    ``xs``/``ys`` carry a leading K axis (one batch per inner step) and the
    returned metrics gain a leading K axis. One dispatch per K steps: the
    host's per-dispatch cost is paid once per K.

    ``grad_accum=N`` splits each shard's batch into N micro-batches and
    accumulates gradients over a ``lax.scan`` — same optimizer math as the
    full batch at 1/N the activation memory (micro-batch moments differ for
    BN, as in every framework). ``remat`` rematerializes the forward during
    backward (``True`` for full remat, or a ``jax.checkpoint`` policy, e.g.
    ``jax.checkpoint_policies.dots_with_no_batch_dims_saveable``) — the
    HBM-for-FLOPs trade the task's hardware notes call for.
    """
    lf = loss_fn or classifier_loss
    mesh = comm.mesh
    axes = comm.axis_names
    dspec = P(axes if len(axes) > 1 else axes[0])

    # A stateful GradReducer (quantized with error feedback) threads
    # per-rank residuals through the optimizer state: their stacked
    # (comm.size, ...) leaves are sharded over the comm axis and
    # (un)stacked around the update — everything else about the step is
    # identical, and the stateless path below compiles the exact same
    # program as before this knob existed.
    reducer = getattr(optimizer, "grad_reducer", None)
    stateful_reducer = bool(getattr(reducer, "stateful", False))
    if stateful_reducer:
        from chainermn_tpu.optimizers import _ReducerWrappedState

    def local_step(state, x, y, rng=None):
        if mutable:
            params, opt_state, extra = state
        else:
            params, opt_state = state
            extra = None
        if stateful_reducer:
            # per-rank residuals arrive stacked-with-leading-1; drop to
            # the rank-local view the reducer works in
            opt_state = _ReducerWrappedState(
                opt_state.inner,
                jax.tree_util.tree_map(lambda r: r[0], opt_state.reducer))

        if rng is not None:
            # decorrelate dropout masks across shards
            for a in axes:
                rng = jax.random.fold_in(rng, lax.axis_index(a))

        if with_rng:
            def f(p, x, y, extra, r):
                return lf(model, p, x, y, train=True, mutable=mutable,
                          extra_vars=extra, rngs={"dropout": r})
        else:
            def f(p, x, y, extra, r):
                return lf(model, p, x, y, train=True, mutable=mutable,
                          extra_vars=extra)

        if remat:
            policy = None if remat is True else remat
            f = jax.checkpoint(f, policy=policy)

        if grad_accum > 1:
            b = x.shape[0]
            assert b % grad_accum == 0, (
                f"per-shard batch {b} not divisible by grad_accum "
                f"{grad_accum}")
            xm = x.reshape((grad_accum, b // grad_accum) + x.shape[1:])
            ym = y.reshape((grad_accum, b // grad_accum) + y.shape[1:])

            def one(extra_c, xi, yi, i):
                # per-micro-batch dropout key
                r = None if rng is None else jax.random.fold_in(rng, i)
                (loss, (acc, new_vars)), g = jax.value_and_grad(
                    f, has_aux=True)(params, xi, yi, extra_c, r)
                new_extra = (
                    {k: new_vars[k] for k in mutable} if mutable else extra_c
                )
                return g, loss, acc, new_extra

            def micro(carry, xyi):
                g_acc, loss_acc, acc_acc, extra_c = carry
                g, loss, acc, new_extra = one(extra_c, *xyi)
                g_acc = jax.tree_util.tree_map(jnp.add, g_acc, g)
                return (g_acc, loss_acc + loss, acc_acc + acc,
                        new_extra), None

            # The first micro-batch runs outside the scan so the carry is
            # initialized with each component's TRUE varying-axis type:
            # grads w.r.t. replicated params arrive already psummed
            # (axis-invariant) under vma tracking — casting a zeros carry to
            # varying here would make allreduce_grad re-reduce them (an N x
            # gradient), while leaving it invariant breaks BN state (varying).
            g0, l0, a0, e0 = one(extra, xm[0], ym[0], 0)
            (g_sum, loss_sum, acc_sum, new_extra), _ = lax.scan(
                micro, (g0, l0, a0, e0),
                (xm[1:], ym[1:], jnp.arange(1, grad_accum)))
            grads = jax.tree_util.tree_map(
                lambda g: g / grad_accum, g_sum)
            loss = loss_sum / grad_accum
            acc = acc_sum / grad_accum
            new_vars = new_extra if mutable else {}
        else:
            (loss, (acc, new_vars)), grads = jax.value_and_grad(
                f, has_aux=True)(params, x, y, extra, rng)
        with jax.named_scope("optimizer_update"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        if stateful_reducer:
            opt_state = _ReducerWrappedState(
                opt_state.inner,
                jax.tree_util.tree_map(lambda r: r[None],
                                       opt_state.reducer))
        metrics = {
            "main/loss": lax.pmean(loss, axes),
            "main/accuracy": lax.pmean(acc, axes),
        }
        if mutable:
            # replica-consistent persistent state (BN running stats)
            new_extra = jax.tree_util.tree_map(
                lambda v: lax.pmean(v, axes)
                if jax.typeof(v).vma else v,
                new_vars,
            )
            return (params, opt_state, new_extra), metrics
        return (params, opt_state), metrics

    if scan_steps > 1:
        single = local_step

        def local_step(state, xs, ys, rng=None):
            def body(state, ixy):
                i, x, y = ixy
                r = None if rng is None else jax.random.fold_in(rng, i)
                return single(state, x, y, r)

            return lax.scan(
                body, state, (jnp.arange(scan_steps), xs, ys))

        # batch axis moves to dim 1 under the leading scan axis
        batch_spec = P(None, axes if len(axes) > 1 else axes[0])
    else:
        batch_spec = dspec

    n_state = 3 if mutable else 2
    if not stateful_reducer:
        in_specs = ((P(),) * n_state, batch_spec, batch_spec)
        if with_rng:
            in_specs = in_specs + (P(),)  # the PRNGKey, replicated
        with _step_build():
            step = jax.jit(
                shard_map(
                    local_step,
                    mesh=mesh,
                    in_specs=in_specs,
                    out_specs=((P(),) * n_state, P()),
                ),
                donate_argnums=(0,) if donate else (),
            )
        return step

    # Stateful reducer: the opt-state specs depend on the state's
    # structure (which leaves are residuals), so compile lazily per
    # treedef — the make_expert_parallel_train_step pattern.
    lead_spec = P(axes if len(axes) > 1 else axes[0])

    def build(state):
        opt_state = state[1]
        if not isinstance(opt_state, _ReducerWrappedState):
            raise ValueError(
                "optimizer carries a stateful grad_reducer but the "
                "opt_state is not reducer-wrapped; initialize with "
                "optimizer.init(params) (outside jit) so the residual "
                "state exists")
        ospecs = _ReducerWrappedState(
            jax.tree_util.tree_map(lambda _: P(), opt_state.inner),
            jax.tree_util.tree_map(lambda _: lead_spec,
                                   opt_state.reducer),
        )
        state_specs = ((P(), ospecs, P()) if mutable else (P(), ospecs))
        in_specs = (state_specs, batch_spec, batch_spec)
        if with_rng:
            in_specs = in_specs + (P(),)
        with _step_build(state):
            return jax.jit(
                shard_map(
                    local_step,
                    mesh=mesh,
                    in_specs=in_specs,
                    out_specs=(state_specs, P()),
                ),
                donate_argnums=(0,) if donate else (),
            )

    compiled = {}

    def step(state, *args):
        key = jax.tree_util.tree_structure(state)
        if key not in compiled:
            compiled[key] = build(state)
        return compiled[key](state, *args)

    return step


def _is_expert_path(path, expert_key: str) -> bool:
    """True for per-shard expert tables. The router lives under the MoE
    module too but is data-parallel (replicated; see ExpertParallelMLP's
    parameter-sync contract), so it is explicitly excluded."""
    parts = [str(getattr(k, "key", k)) for k in path]
    return (any(expert_key in p for p in parts)
            and not any("router" in p for p in parts))


def init_expert_parallel_state(model, comm, rng, sample, optimizer,
                               expert_key: str = "moe"):
    """Initialize a model containing expert-parallel layers.

    Expert leaves (param path containing ``expert_key``) are per-shard:
    each mesh shard initializes its own experts (rank-folded RNG) and the
    global array concatenates them over the comm axis (sharded ``P(ax)``).
    Every other leaf is replicated — shard 0's init wins.

    Returns ``(state, param_specs)`` where ``state = (params, opt_state)``
    and ``param_specs`` is the PartitionSpec pytree
    (make_expert_parallel_train_step needs it).
    """
    mesh = comm.mesh
    ax = comm.axis_names[0]

    def init_fn(toks):
        r = jax.random.fold_in(rng, lax.axis_index(ax))
        params = model.init(r, toks)["params"]

        def fix(path, leaf):
            if _is_expert_path(path, expert_key):
                return leaf                       # this shard's experts
            return lax.all_gather(leaf, ax)[0]    # replicate shard 0's init

        return jax.tree_util.tree_map_with_path(fix, params)

    # structure discovery pass (shapes only — out_specs don't matter here)
    abs_params = jax.eval_shape(
        shard_map(init_fn, mesh=mesh, in_specs=(P(),), out_specs=P(),
                  check_vma=False),
        sample,
    )
    param_specs = jax.tree_util.tree_map_with_path(
        lambda path, _: P(ax) if _is_expert_path(path, expert_key) else P(),
        abs_params,
    )
    with _step_build() as sp:
        params = jax.jit(shard_map(
            init_fn, mesh=mesh, in_specs=(P(),), out_specs=param_specs,
            check_vma=False,
        ))(sample)
        opt_state = jax.jit(optimizer.init)(params)  # shardings follow params
        sp.set(param_bytes=_tree_bytes(params),
               opt_state_bytes=_tree_bytes(opt_state))
    return (params, opt_state), param_specs


def make_expert_parallel_train_step(
    model,
    optimizer: optax.GradientTransformation,
    comm,
    param_specs,
    loss_fn: Optional[Callable] = None,
    expert_key: str = "moe",
    donate: bool = True,
):
    """Train step for models with expert-parallel (MoE) layers.

    Shared parameters are data-parallel (replicated; their gradients are
    globally reduced by shard_map's replication typing — do NOT wrap the
    optimizer in create_multi_node_optimizer here, that would re-reduce).
    Expert parameters stay sharded over the comm axis: each shard owns and
    updates its experts; their gradients already aggregate every shard's
    tokens through the all_to_all transpose, so no collective touches them.

    ``param_specs`` comes from init_expert_parallel_state. ``optimizer`` is
    a PLAIN optax transformation.
    """
    lf = loss_fn or classifier_loss
    mesh = comm.mesh
    axes = comm.axis_names
    dspec = P(axes if len(axes) > 1 else axes[0])

    def local_step(state, x, y):
        params, opt_state = state

        def f(p):
            loss, (acc, _) = lf(model, p, x, y, train=True)
            # global-mean objective; expert grads flow through the
            # all_to_all transpose, shared grads through replication typing
            return lax.pmean(loss, axes), acc

        (loss, acc), grads = jax.value_and_grad(f, has_aux=True)(params)
        with jax.named_scope("optimizer_update"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        metrics = {
            "main/loss": loss,
            "main/accuracy": lax.pmean(acc, axes),
        }
        return (params, opt_state), metrics

    def opt_spec_like(tree):
        """Specs over an opt-state pytree: leaves on an expert path are
        sharded, the rest (incl. step counters) replicated."""
        # same single-axis sharding as param_specs (axes[0]) — a multi-axis
        # spec here would disagree with the params' local shapes
        return jax.tree_util.tree_map_with_path(
            lambda path, leaf: P(axes[0])
            if _is_expert_path(path, expert_key) and getattr(leaf, "ndim", 0)
            else P(),
            tree,
        )

    def build(state):
        params, opt_state = state
        opt_specs = opt_spec_like(opt_state)
        with _step_build(state):
            return jax.jit(
                shard_map(
                    local_step,
                    mesh=mesh,
                    in_specs=((param_specs, opt_specs), dspec, dspec),
                    out_specs=(((param_specs, opt_specs)), P()),
                ),
                donate_argnums=(0,) if donate else (),
            )

    compiled = {}

    def step(state, x, y):
        key = jax.tree_util.tree_structure(state)
        if key not in compiled:
            compiled[key] = build(state)
        return compiled[key](state, x, y)

    return step


def make_eval_step(model, comm, loss_fn: Optional[Callable] = None,
                   extra_vars_in_state: bool = False):
    """Jitted eval step: (state, x, y) -> metrics dict (pmean-reduced)."""
    lf = loss_fn or classifier_loss
    mesh = comm.mesh
    axes = comm.axis_names
    dspec = P(axes if len(axes) > 1 else axes[0])

    def local_eval(state, x, y):
        params = state[0]
        extra = state[2] if extra_vars_in_state else None
        loss, (acc, _) = lf(model, params, x, y, train=False,
                            mutable=None, extra_vars=extra)
        return {
            "validation/main/loss": lax.pmean(loss, axes),
            "validation/main/accuracy": lax.pmean(acc, axes),
        }

    n_state = 3 if extra_vars_in_state else 2
    return jax.jit(
        shard_map(
            local_eval,
            mesh=mesh,
            in_specs=((P(),) * n_state, dspec, dspec),
            out_specs=P(),
        )
    )
