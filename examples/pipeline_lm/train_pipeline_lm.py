#!/usr/bin/env python
"""Pipeline-parallel Transformer LM — two schedules on a real model.

Default: the interleaved 1F1B schedule with composition hooks
(parallel/pipeline.py): embedding runs outside the pipeline (its gradient
returns through ``input_grads``), TransformerBlocks are the homogeneous
stages — logical stage v*S+d on device d (virtual chunks) — and the LM
head trains inside ``loss_fn`` via ``head_params``. One optax update
covers all three parameter groups.

``--hetero``: embedding and head are ORDINARY stages
(parallel/hetero_pipeline.py) — the int32→[mb,L,D] shape changes ride the
flat activation wire sized by the widest TRAVELING edge (the [mb,L,vocab]
logits die in the local loss and never touch the ring), the whole model's
parameters are one [S, P] stack sharded over the stage axis, and a single
optax.adam on that stack is the whole-model optimizer. No hooks in user
code — the head-in-loss routing is internal to HeteroPipeline.

Beyond the reference's surface either way: upstream pipeline usage is
MultiNodeChainList's sequential fill/drain (SURVEY.md §2.6); this example
is the micro-batched schedule on a real LM.

Run (8 virtual devices):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/pipeline_lm/train_pipeline_lm.py --steps 20
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import jax
import flax.linen as nn
import jax.numpy as jnp
import optax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from chainermn_tpu.models.transformer import TransformerBlock
from chainermn_tpu.parallel import (
    HeteroPipeline,
    hetero_pipeline_1f1b_value_and_grad,
    pipeline_interleaved_1f1b_value_and_grad,
    stack_stage_params,
)


class EmbedIn(nn.Module):
    vocab: int
    d_model: int
    max_len: int

    @nn.compact
    def __call__(self, toks):
        x = nn.Embed(self.vocab, self.d_model, name="tok")(toks)
        pos = self.param("pos", nn.initializers.normal(0.02),
                         (self.max_len, self.d_model))
        return x + pos[None, : toks.shape[-1]]


class HeadOut(nn.Module):
    """LM head. ONE architecture definition for both the replicated and
    tensor-parallel paths: under TP, instantiate with ``vocab`` = the
    LOCAL vocab slice (full_vocab // T) and ``tp_axis`` set — the kernel
    arrives column-sharded via in_specs (init the FULL kernel with a
    plain ``HeadOut(full_vocab)``; the param trees match), and the
    Megatron f-operator at the column-parallel entry makes LayerNorm
    grads and the input cotangent full per shard."""

    vocab: int
    tp_axis: str = None

    @nn.compact
    def __call__(self, h):
        h = nn.LayerNorm()(h)
        if self.tp_axis is not None:
            from chainermn_tpu.parallel.tensor_parallel import (
                copy_to_tp_region)

            h = copy_to_tp_region(h, self.tp_axis)
        return nn.Dense(self.vocab, use_bias=False, name="out")(h)


def _train_loop(train_step, params, opt_state, args, M):
    """Shared synthetic-data generator + timed loop for both modes —
    cyclic-vocab next-token sequences with learnable structure."""
    data_rng = np.random.RandomState(0)

    def batch():
        start = data_rng.randint(0, args.vocab,
                                 size=(M, args.mb_size, 1))
        seq = (start + np.arange(args.seq_len + 1)) % args.vocab
        return (jnp.asarray(seq[..., :-1], jnp.int32),
                jnp.asarray(seq[..., 1:], jnp.int32))

    t0 = time.perf_counter()
    for step in range(args.steps):
        toks, tgts = batch()
        params, opt_state, loss = train_step(params, opt_state, toks, tgts)
        if step == 0 or (step + 1) % 10 == 0:
            print(f"step {step + 1:4d}  loss {float(loss):.4f}  "
                  f"({time.perf_counter() - t0:.1f}s)")
    print(f"final loss: {float(loss):.4f}")
    return float(loss)


def main_hetero(args):
    """Embed → blocks → head, every one an ORDINARY pipeline stage.

    No composition hooks in user code: the embedding's int32→[mb,L,D]
    shape change rides HeteroPipeline's flat wire — sized mb·L·d_model,
    because the head's [mb,L,vocab] logits never travel the ring — and
    the whole model's parameters live as ONE [S, P] f32 stack sharded
    over the stage axis, so a single optax.adam over that array IS the
    whole-model optimizer, with each device updating only its stage's row.
    """

    S = args.n_pipeline or jax.device_count()
    n_blocks = S - 2
    if n_blocks < 1:
        raise SystemExit("--hetero needs S >= 3 (embed + blocks + head)")
    M = args.microbatches or 2 * S
    mesh = Mesh(np.array(jax.devices()[:S]), ("stage",))
    print(f"hetero pipeline: {S} stages = embed + {n_blocks} blocks + "
          f"head, {M} micro-batches of {args.mb_size}x{args.seq_len}")

    block = TransformerBlock(
        d_model=args.d_model, n_heads=args.n_heads, d_ff=args.d_ff,
        attention=args.attention)
    embed = EmbedIn(args.vocab, args.d_model, args.seq_len)
    head = HeadOut(args.vocab)

    rng = jax.random.PRNGKey(0)
    toks0 = np.zeros((args.mb_size, args.seq_len), np.int32)
    h0 = np.zeros((args.mb_size, args.seq_len, args.d_model), np.float32)
    stage_defs = [(lambda p, t: embed.apply({"params": p}, t),
                   embed.init(rng, toks0)["params"])]
    stage_defs += [
        (lambda p, h: block.apply({"params": p}, h),
         block.init(jax.random.fold_in(rng, k), h0)["params"])
        for k in range(n_blocks)
    ]
    stage_defs += [(lambda p, h: head.apply({"params": p}, h),
                    head.init(jax.random.fold_in(rng, 999), h0)["params"])]

    pipe = HeteroPipeline(
        stage_defs, jax.ShapeDtypeStruct((args.mb_size, args.seq_len),
                                         jnp.int32), axis_name="stage")
    # the wire is d_model-wide, not vocab-wide: logits never travel
    assert pipe.wire_elems == args.mb_size * args.seq_len * args.d_model
    packed = jax.device_put(pipe.pack_params(),
                            NamedSharding(mesh, P("stage")))
    opt = optax.adam(args.lr)
    opt_state = jax.jit(opt.init)(packed)

    def loss_fn(logits, tgt):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, tgt).mean()

    def run(stacked, xw, tgts):
        my = jax.tree_util.tree_map(lambda l: l[0], stacked)
        loss, g = hetero_pipeline_1f1b_value_and_grad(
            pipe, loss_fn, my, xw, tgts)
        return loss, g[None]

    run_sm = shard_map(run, mesh=mesh, in_specs=(P("stage"), P(), P()),
                       out_specs=(P(), P("stage")))

    @jax.jit
    def train_step(packed, opt_state, toks, tgts):
        xw = pipe.encode_inputs(toks)
        loss, grads = run_sm(packed, xw, tgts)
        updates, opt_state = opt.update(grads, opt_state, packed)
        return optax.apply_updates(packed, updates), opt_state, loss

    return _train_loop(train_step, packed, opt_state, args, M)


def main():
    p = argparse.ArgumentParser(
        description="ChainerMN-TPU example: pipeline-parallel LM")
    p.add_argument("--stages-per-device", "-V", type=int, default=2)
    p.add_argument("--tp", type=int, default=1, metavar="T",
                   help="Megatron tensor parallelism INSIDE each "
                        "pipeline stage on a (stage, model) mesh: "
                        "column/row-parallel attention + MLP per block, "
                        "psums over 'model' riding inside the 1F1B "
                        "schedule (VERDICT r2 #6 composition)")
    p.add_argument("--n-pipeline", "-S", type=int, default=None,
                   help="pipeline depth in devices (default: all)")
    p.add_argument("--microbatches", "-M", type=int, default=None,
                   help="micro-batches per step (default: 2*S)")
    p.add_argument("--mb-size", type=int, default=4)
    p.add_argument("--seq-len", "-L", type=int, default=32)
    p.add_argument("--vocab", type=int, default=64)
    p.add_argument("--d-model", type=int, default=64)
    p.add_argument("--n-heads", type=int, default=4)
    p.add_argument("--d-ff", type=int, default=128)
    p.add_argument("--attention", default="flash",
                   choices=["flash", "reference"])
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument("--hetero", action="store_true",
                   help="run embedding and head as ORDINARY pipeline "
                        "stages (HeteroPipeline: flat activation/param "
                        "wires + switch dispatch, classic 1F1B) instead "
                        "of the head_params/input_grads composition hooks")
    args = p.parse_args()

    if args.hetero:
        return main_hetero(args)

    T = max(args.tp, 1)
    S = args.n_pipeline or (jax.device_count() // T)
    V = args.stages_per_device
    M = args.microbatches or 2 * S
    N = S * V
    if S < 1 or S * T > jax.device_count():
        raise SystemExit(f"need SxT = {S}x{T} devices, have "
                         f"{jax.device_count()}")
    if T > 1 and args.n_heads % T:
        raise SystemExit(f"--tp {T} must divide --n-heads {args.n_heads}")
    if T > 1 and args.vocab % T:
        raise SystemExit(f"--tp {T} must divide --vocab {args.vocab}")
    mesh = Mesh(np.array(jax.devices()[:S * T]).reshape(S, T),
                ("stage", "model"))
    print(f"pipeline: {S} stage devices x {V} chunks = {N} blocks"
          + (f", TP {T} (mesh stage x model)" if T > 1 else "")
          + f", {M} micro-batches of {args.mb_size}x{args.seq_len}")

    block = TransformerBlock(
        d_model=args.d_model, n_heads=args.n_heads, d_ff=args.d_ff,
        attention=args.attention, tp_axis="model" if T > 1 else None)
    embed = EmbedIn(args.vocab, args.d_model, args.seq_len)
    head = HeadOut(args.vocab // T if T > 1 else args.vocab,
                   tp_axis="model" if T > 1 else None)

    rng = jax.random.PRNGKey(0)
    toks0 = np.zeros((args.mb_size, args.seq_len), np.int32)
    h0 = np.zeros((args.mb_size, args.seq_len, args.d_model), np.float32)
    emb_p = embed.init(rng, toks0)["params"]
    if T > 1:
        # TP params must be initialized per (stage, model) shard — inside
        # shard_map, same rng along 'model' so REPLICATED leaves
        # (LayerNorms) start identical across the model axis (the
        # Megatron f-operator keeps them in sync from there; TP slices
        # are rng-tied, which only correlates the init, see
        # tests/parallel_tests/test_tp_transformer.py)
        def init_stages(h0):
            s = jax.lax.axis_index("stage")
            ps = [
                block.init(jax.random.fold_in(rng, v * S + s), h0)["params"]
                for v in range(V)
            ]
            p = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *ps)
            return jax.tree_util.tree_map(lambda l: l[:, None, None], p)

        stage_p = jax.jit(shard_map(
            init_stages, mesh=mesh, in_specs=P(),
            out_specs=P(None, "stage", "model"), check_vma=False))(
                jnp.asarray(h0))
    else:
        stage_p = stack_stage_params([
            block.init(jax.random.fold_in(rng, k), h0)["params"]
            for k in range(N)])
        stage_p = jax.tree_util.tree_map(
            lambda q: q.reshape((V, S) + q.shape[1:]), stage_p)
    # init the FULL kernel (same param tree as the TP apply-instance)
    head_p = HeadOut(args.vocab).init(
        jax.random.fold_in(rng, 999), h0)["params"]
    if T > 1:
        # VOCAB-PARALLEL head: LayerNorm replicated, Dense kernel
        # column-sharded over 'model' — the full [mb, L, vocab] logits
        # never materialize; the loss hook admits the psums because the
        # cond predicate is uniform along 'model' (see
        # parallel/pipeline.py:_head_loss_grads). shard_map specs are
        # tree prefixes: one P() covers the LayerNorm subtree.
        hspec = {"LayerNorm_0": P(), "out": {"kernel": P(None, "model")}}
        head_p = {
            "LayerNorm_0": jax.device_put(
                head_p["LayerNorm_0"], NamedSharding(mesh, P())),
            "out": {"kernel": jax.device_put(
                head_p["out"]["kernel"],
                NamedSharding(mesh, P(None, "model")))},
        }
    else:
        hspec = P()
    params = (emb_p, stage_p, head_p)
    opt = optax.adam(args.lr)
    opt_state = opt.init(params)

    def head_loss(hp, out, tgt):
        # ONE architecture: HeadOut applies the sharded kernel as-is
        # (logits come back [mb, L, vocab/T] under TP)
        logits = head.apply({"params": hp}, out)
        if T > 1:
            from chainermn_tpu.parallel.tensor_parallel import (
                vocab_parallel_cross_entropy)

            return jnp.mean(
                vocab_parallel_cross_entropy(logits, tgt, "model"))
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, tgt).mean()

    def stage_fn(sp, h):
        return block.apply({"params": sp}, h)

    # stage params stack: [V, S(sharded), ...] — with TP a third
    # 'model'-sharded axis. In-shard both singleton axes are stripped.
    n_lead = 2 if T > 1 else 1
    stage_spec = (P(None, "stage", "model") if T > 1
                  else P(None, "stage"))

    def pipe(sp, hp, x_mb, tgts):
        for _ in range(n_lead):
            sp = jax.tree_util.tree_map(lambda q: q.squeeze(1), sp)
        loss, g, aux = pipeline_interleaved_1f1b_value_and_grad(
            stage_fn, head_loss, sp, x_mb, tgts, "stage", V,
            head_params=hp, return_input_grads=True)
        hg, dxs = aux["head_grads"], aux["input_grads"]
        if T > 1:
            # loss/input-grads/LN-grads are equal along 'model' (the
            # f-operator psums cotangents; vocab-parallel CE psums the
            # loss terms); pmean resolves their vma to invariant. The
            # head KERNEL grads are genuinely sharded — left varying.
            loss = jax.lax.pmean(loss, "model")
            hg = {"LayerNorm_0": jax.tree_util.tree_map(
                lambda q: jax.lax.pmean(q, "model"), hg["LayerNorm_0"]),
                "out": hg["out"]}
            dxs = jax.lax.pmean(dxs, "model")
        for _ in range(n_lead):
            g = jax.tree_util.tree_map(lambda q: q[:, None], g)
        return (loss, g, hg, dxs)

    pipe_sm = shard_map(
        pipe, mesh=mesh,
        in_specs=(stage_spec, hspec, P(), P()),
        out_specs=(P(), stage_spec, hspec, P()))

    @jax.jit
    def train_step(params, opt_state, toks, tgts):
        emb_p, stage_p, head_p = params
        x_mb, emb_vjp = jax.vjp(
            lambda ep: jax.vmap(
                lambda t: embed.apply({"params": ep}, t))(toks), emb_p)
        loss, sgrads, hgrads, dxs = pipe_sm(stage_p, head_p, x_mb, tgts)
        (degrads,) = emb_vjp(dxs)
        grads = (degrads, sgrads, hgrads)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    return _train_loop(train_step, params, opt_state, args, M)


if __name__ == "__main__":
    # supervisor exit-status contract (docs/fault_tolerance.md):
    # 0 clean, 143 preempted-and-checkpointed, 75 watchdog abort
    from chainermn_tpu.resilience.supervisor import main_exit_code
    sys.exit(main_exit_code(main))
