#!/usr/bin/env python
"""Data-parallel Transformer LM on synthetic text — the long-context example.

Beyond-reference example (the reference's sequence model is an LSTM
seq2seq): a decoder-only causal LM with flash attention, trained
data-parallel like every other example, plus two sharded variants:

* ``--ring``: sequence parallelism — the sequence axis is sharded over the
  mesh and attention runs as ring attention (ppermute-rotated KV blocks);
* ``--moe N``: the FFN becomes a Switch MoE with N experts per device,
  experts sharded over the mesh (expert parallelism).

Run (virtual 8-device CPU mesh):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/transformer_lm/train_lm.py --epoch 2
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import jax
import numpy as np
import optax

import chainermn_tpu

from chainermn_tpu.iterators import SerialIterator
from chainermn_tpu.models.transformer import TransformerLM, lm_loss_with_aux
from chainermn_tpu.training import (
    LogReport,
    PrintReport,
    StandardUpdater,
    Trainer,
)
from chainermn_tpu.training.step import make_data_parallel_train_step


def synthetic_text(n: int, length: int, vocab: int, seed: int = 0):
    """Cyclic sequences with a per-sample stride — learnable structure."""
    rng = np.random.RandomState(seed)
    starts = rng.randint(0, vocab, size=n)
    strides = rng.randint(1, 4, size=n)
    pos = np.arange(length + 1)
    seq = (starts[:, None] + strides[:, None] * pos[None]) % vocab
    return [(seq[i, :-1].astype(np.int32), seq[i, 1:].astype(np.int32))
            for i in range(n)]


def main():
    p = argparse.ArgumentParser(
        description="ChainerMN-TPU example: Transformer LM")
    p.add_argument("--batchsize", "-b", type=int, default=64)
    p.add_argument("--epoch", "-e", type=int, default=3)
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--vocab", type=int, default=64)
    p.add_argument("--d-model", type=int, default=128)
    p.add_argument("--n-heads", type=int, default=4)
    p.add_argument("--n-layers", type=int, default=2)
    p.add_argument("--communicator", type=str, default="xla")
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument("--n-train", type=int, default=2048)
    p.add_argument("--moe", type=int, default=0, metavar="N",
                   help="experts per device (0 = dense FFN)")
    p.add_argument("--moe-top-k", type=int, default=1,
                   help="experts per token (1 = Switch, 2 = GShard)")
    p.add_argument("--ring", action="store_true",
                   help="sequence-parallel attention demo after "
                        "training (implementation: --seq-impl)")
    p.add_argument("--seq-impl", choices=["ring", "ring_flash",
                                          "ulysses"], default="ring",
                   help="sequence-parallel attention used by --ring")
    p.add_argument("--fsdp-scan", action="store_true",
                   help="FSDP over a SCANNED layer stack: stack_lm_blocks"
                        " + make_lm_fsdp_scan_loss — the compiler-forced "
                        "per-layer gather bound (peak gathered params = "
                        "one layer) with the fused head+CE loss; needs "
                        "vocab % 128 == 0")
    p.add_argument("--zero", type=int, default=0, choices=[0, 1, 2, 3],
                   help="ZeRO stage: 1 = sharded optimizer state, 2 = +"
                        "sharded grad accumulator (2 microbatches), "
                        "3 = FSDP per-leaf param sharding")
    p.add_argument("--zero-bucket-kib", type=int, default=0,
                   help="with --zero 1/2: reduce-scatter per KiB-sized "
                        "gradient bucket (kills the transient full "
                        "gradient)")
    p.add_argument("--qkv-layout", choices=["blhd", "bhld"],
                   default="blhd",
                   help="bhld: head-major pivot-free attention tensors "
                        "(+3%% measured on the flash path — BASELINE.md; "
                        "decode/generation needs blhd)")
    p.add_argument("--n-kv-heads", type=int, default=0, metavar="K",
                   help="KV heads < query heads = GQA/MQA (0 = all)")
    p.add_argument("--window", type=int, default=0, metavar="W",
                   help="sliding-window attention span (0 = full)")
    p.add_argument("--rope", action="store_true",
                   help="rotary position embeddings instead of a "
                        "learned table")
    p.add_argument("--autotune-blocks", action="store_true",
                   help="time the flash-attention (block_q, block_k) "
                        "candidates for this exact shape "
                        "(ops/autotune.py) and build the model with the "
                        "winner; off-TPU the tuner returns the defaults "
                        "untimed, so the flag is a no-op there")
    p.add_argument("--text-file", default=None,
                   help="train from a REAL text file: byte-BPE tokenize "
                        "(vocab from --bpe-vocab, cached next to the "
                        "file), concatenate, and chop into --seq-len "
                        "next-token windows — the standard LM data prep")
    p.add_argument("--bpe-vocab", type=int, default=512,
                   help="BPE vocabulary size for --text-file")
    p.add_argument("--out", "-o", default="result_lm")
    args = p.parse_args()

    comm = chainermn_tpu.create_communicator(args.communicator)
    if comm.is_master:
        print(f"devices: {comm.size}  mesh axes: {comm.axis_names}")

    if args.text_file:
        from chainermn_tpu.datasets import BPETokenizer, train_bpe_file

        cache = args.text_file + f".bpe{args.bpe_vocab}.json"
        tok = train_bpe_file(args.text_file, args.bpe_vocab,
                             cache_path=cache)
        with open(args.text_file, encoding="utf-8") as f:
            ids = np.asarray(tok.encode(f.read(), eos=True), np.int32)
        args.vocab = tok.vocab_size
        L = args.seq_len
        if len(ids) < L + 1:
            raise SystemExit(
                f"--text-file encodes to only {len(ids)} tokens — need "
                f"at least seq_len+1 = {L + 1} for one training window; "
                "use a longer file or a smaller --seq-len")
        n_win = (len(ids) - 1) // L
        train = [(ids[i * L:i * L + L], ids[i * L + 1:i * L + L + 1])
                 for i in range(n_win)]
        if comm.is_master:
            print(f"text: {len(ids)} tokens, BPE vocab {args.vocab}, "
                  f"{len(train)} windows of {L} ({cache})")
    else:
        train = synthetic_text(args.n_train, args.seq_len, args.vocab,
                               seed=0)
    train = chainermn_tpu.scatter_dataset(train, comm, shuffle=True, seed=0)

    # the Pallas kernels are the path on a TPU; the CPU mesh takes the
    # XLA reference because the interpreted kernels are slow, not wrong.
    # Any other backend is refused rather than handed the reference.
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise SystemExit(f"unsupported backend {backend!r}: the attention "
                         "kernels target TPU (CPU runs the reference)")
    attention = "flash" if backend == "tpu" else "reference"
    if args.window or args.qkv_layout == "bhld" or args.n_kv_heads:
        attention = "flash"  # interpreted on the CPU; required for window,
        #                      GQA and the head-major bhld layout
    if comm.is_master:
        print(f"backend: {backend}  attention: {attention}")
    lm_kw = dict(
        n_kv_heads=args.n_kv_heads or None,
        attention_window=args.window or None,
        pos_emb="rope" if args.rope else "learned",
        qkv_layout=args.qkv_layout,
    )
    if args.autotune_blocks:
        import jax.numpy as jnp

        from chainermn_tpu.ops.autotune import tune_flash_blocks

        bq, bk = tune_flash_blocks(
            max(1, args.batchsize // comm.size), args.seq_len,
            args.n_heads, args.d_model // args.n_heads,
            kv_heads=args.n_kv_heads or None, dtype=jnp.float32,
            window=args.window or None)
        lm_kw["attention_blocks"] = (bq, bk)
        if comm.is_master:
            print(f"autotuned flash blocks: block_q={bq} block_k={bk}")
    sample = np.zeros((1, args.seq_len), np.int32)
    if args.fsdp_scan and args.moe > 0:
        # make_lm_fsdp_scan_loss would refuse MoE anyway, but the MoE
        # branch below is taken first — fail HERE instead of silently
        # dropping the flag
        raise SystemExit("--fsdp-scan does not compose with --moe (the "
                         "load-balancing aux cannot thread through the "
                         "scan)")
    if args.moe > 0:
        from chainermn_tpu.training.step import (
            init_expert_parallel_state,
            make_expert_parallel_train_step,
        )

        model = TransformerLM(
            vocab=args.vocab, d_model=args.d_model, n_heads=args.n_heads,
            n_layers=args.n_layers, d_ff=4 * args.d_model,
            max_len=args.seq_len, attention=attention, **lm_kw,
            moe_experts_per_device=args.moe,
            expert_axis=comm.axis_names[0], capacity_factor=2.0,
            moe_top_k=args.moe_top_k)
        optimizer = optax.adam(args.lr)  # plain: expert grads stay local
        state, param_specs = init_expert_parallel_state(
            model, comm, jax.random.PRNGKey(0), sample, optimizer)
        step = make_expert_parallel_train_step(
            model, optimizer, comm, param_specs, loss_fn=lm_loss_with_aux)
    else:
        model = TransformerLM(
            vocab=args.vocab, d_model=args.d_model, n_heads=args.n_heads,
            n_layers=args.n_layers, d_ff=4 * args.d_model,
            max_len=args.seq_len, attention=attention, **lm_kw)
        params = model.init(jax.random.PRNGKey(0), sample)["params"]
        params = comm.bcast_data(params)
        if args.fsdp_scan:
            # the r5 flagship FSDP form (models/transformer.py
            # make_lm_fsdp_scan_loss): layer stack scanned, one layer
            # gathered at a time, re-gathered in backward
            if args.zero:
                raise SystemExit("--fsdp-scan and --zero are exclusive")
            if args.vocab % 128:
                raise SystemExit("--fsdp-scan needs vocab % 128 == 0 "
                                 "(fused head+CE vocab tile)")
            from chainermn_tpu.models.transformer import (
                make_lm_fsdp_scan_loss, stack_lm_blocks)
            from chainermn_tpu.optimizers import (fsdp_shardings,
                                                  fsdp_stack_shardings,
                                                  make_fsdp_train_step)

            packed = stack_lm_blocks(params)
            shardings = dict(
                fsdp_shardings(packed, comm),
                blocks=fsdp_stack_shardings(packed, comm)["blocks"])
            step, state = make_fsdp_train_step(
                None, optax.adam(args.lr), comm, packed,
                loss_fn=make_lm_fsdp_scan_loss(model),
                param_shardings=shardings)
        elif args.zero:
            # sharded training (beyond reference, optimizers/zero.py):
            # adam m/v live 1/N per device; --zero-bucket-kib additionally
            # reduce-scatters each gradient bucket as backward produces
            # it, so the full-model gradient never exists as one buffer
            from chainermn_tpu.optimizers import (make_fsdp_train_step,
                                                  make_zero1_train_step,
                                                  make_zero2_train_step)

            bb = (args.zero_bucket_kib * 1024
                  if args.zero_bucket_kib else None)
            if args.zero == 3 and bb:
                raise SystemExit(
                    "--zero-bucket-kib applies to --zero 1/2 only: FSDP "
                    "gradient liveness follows XLA's per-leaf schedule, "
                    "not the bucket plan")
            if args.zero == 1:
                step, state = make_zero1_train_step(
                    model, optax.adam(args.lr), comm, params,
                    loss_fn=lm_loss_with_aux, bucket_bytes=bb)
            elif args.zero == 2:
                step, state = make_zero2_train_step(
                    model, optax.adam(args.lr), comm, params,
                    n_microbatches=2, loss_fn=lm_loss_with_aux,
                    bucket_bytes=bb)
            else:
                step, state = make_fsdp_train_step(
                    model, optax.adam(args.lr), comm, params,
                    loss_fn=lm_loss_with_aux)
        else:
            optimizer = chainermn_tpu.create_multi_node_optimizer(
                optax.adam(args.lr), comm)
            state = (params, optimizer.init(params))
            step = make_data_parallel_train_step(
                model, optimizer, comm, loss_fn=lm_loss_with_aux)

    train_it = SerialIterator(train, args.batchsize, shuffle=True, seed=0)
    updater = StandardUpdater(train_it, step, state, comm)
    trainer = Trainer(updater, stop_trigger=(args.epoch, "epoch"),
                      out=args.out)

    if comm.is_master:
        trainer.extend(LogReport(os.path.join(args.out, "log.jsonl")),
                       trigger=(1, "epoch"))
        trainer.extend(PrintReport(
            ["epoch", "iteration", "main/loss", "main/accuracy",
             "elapsed_time"]), trigger=(1, "epoch"))

    trainer.run()
    # preempted runs have no final observation — and must not crash
    # here, or exit 143 never reaches the supervisor
    if comm.is_master and not trainer.preempted:
        final = trainer.observation
        print(f"final: loss={final.get('main/loss'):.4f} "
              f"acc={final.get('main/accuracy'):.4f}")

    if args.ring and (args.moe > 0 or args.n_kv_heads or args.zero
                      or args.fsdp_scan or args.qkv_layout != "blhd"):
        if comm.is_master:
            print("--ring demo skipped: it reuses the trained params, and "
                  "a MoE/GQA/ZeRO/fsdp-scan/bhld run produces a different "
                  "param structure/layout than the sequence-parallel "
                  "model expects")
    elif args.ring and args.seq_impl == "ulysses" and (
            args.n_heads % comm.size):
        if comm.is_master:
            print(f"--ring demo skipped: ulysses needs --n-heads "
                  f"divisible by the {comm.size}-device axis")
    elif args.ring:
        # sequence-parallel inference: shard the sequence over the mesh,
        # positions stay global via pos_offset
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        ax = comm.axis_names[0]
        ring = TransformerLM(
            vocab=args.vocab, d_model=args.d_model, n_heads=args.n_heads,
            n_layers=args.n_layers, d_ff=4 * args.d_model,
            max_len=args.seq_len, attention=args.seq_impl, seq_axis=ax,
            pos_emb="rope" if args.rope else "learned")
        l_local = args.seq_len // comm.size
        toks = np.asarray(train[0][0])[None]

        def f(params, toks_local):
            off = jax.lax.axis_index(ax) * l_local
            return ring.apply({"params": params}, toks_local,
                              pos_offset=off)

        params_now = updater.state[0]
        logits = jax.jit(shard_map(
            f, mesh=comm.mesh, in_specs=(P(), P(None, ax)),
            out_specs=P(None, ax)))(params_now, toks)
        pred = np.asarray(logits).argmax(-1)
        acc = float((pred[0] == np.asarray(train[0][1])).mean())
        if comm.is_master:
            print(f"{args.seq_impl}-attention (seq sharded over "
                  f"{comm.size} devices) next-token acc: {acc:.4f}")
    return trainer


if __name__ == "__main__":
    # supervisor exit-status contract (docs/fault_tolerance.md):
    # 0 clean, 143 preempted-and-checkpointed, 75 watchdog abort
    from chainermn_tpu.resilience.supervisor import main_exit_code
    sys.exit(main_exit_code(main))
