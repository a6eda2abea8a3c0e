#!/usr/bin/env python
"""Data-parallel seq2seq (BASELINE config #4 — variable-length batches,
scatter_dataset / object-plane path).

Reference: examples/seq2seq/seq2seq.py (WMT En-De, LSTM encoder-decoder,
per-rank scattered variable-length samples). Here variable-length pairs ride
the object plane in scatter_dataset, batches are padded into fixed length
buckets (static shapes for XLA — the TPU answer to dynamic batching), and
the masked-loss training step compiles once per bucket shape.

Data: ``--src-file``/``--tgt-file`` read a REAL parallel text corpus from
disk and byte-BPE-tokenize it (chainermn_tpu.datasets.bpe — the
reference's WMT vocabulary step; generate a local corpus with
examples/seq2seq/make_corpus.py). Without them, synthetic
reversal-translation id pairs stand in (no network egress); any list of
(src_ids, tgt_ids) pairs drops in.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import jax
import jax.numpy as jnp
import numpy as np
import optax
import chainermn_tpu

from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P
from chainermn_tpu.datasets.toy import synthetic_translation
from chainermn_tpu.iterators import SerialIterator
from chainermn_tpu.models.seq2seq import Seq2Seq, pad_batch, seq2seq_loss


def main():
    p = argparse.ArgumentParser(description="ChainerMN-TPU example: seq2seq")
    p.add_argument("--batchsize", "-b", type=int, default=64)
    p.add_argument("--epoch", "-e", type=int, default=2)
    p.add_argument("--unit", "-u", type=int, default=128)
    p.add_argument("--layer", "-l", type=int, default=2)
    p.add_argument("--communicator", type=str, default="xla")
    p.add_argument("--vocab", type=int, default=1000)
    p.add_argument("--n-train", type=int, default=1024)
    p.add_argument("--beam", type=int, default=0, metavar="K",
                   help="post-training translate demo: beam width "
                        "(0 = greedy)")
    p.add_argument("--bucket", type=int, default=32,
                   help="pad lengths to multiples of this")
    p.add_argument("--src-file", default=None,
                   help="source-side text file (one sentence per line); "
                        "tokenized with byte-BPE trained on the corpus")
    p.add_argument("--tgt-file", default=None,
                   help="target-side text file (parallel to --src-file)")
    p.add_argument("--bpe-vocab", type=int, default=512,
                   help="BPE vocabulary size for --src-file/--tgt-file "
                        "(specials + bytes + merges)")
    args = p.parse_args()

    comm = chainermn_tpu.create_communicator(args.communicator)
    if comm.is_master:
        print(f"devices: {comm.size}")

    # variable-length Python objects — the object-plane data path. Only
    # the root builds the dataset; the actual pickled samples ship in
    # chunks over the plane (reference scatter_dataset semantics), so
    # workers need no access to the root's storage.
    vocab = args.vocab
    if args.src_file or args.tgt_file:
        # REAL parallel text from disk, byte-BPE tokenized — the
        # reference's WMT vocabulary + encode step (upstream
        # examples/seq2seq/seq2seq.py; SURVEY.md §3.4). The vocabulary
        # artifact is cached next to the source file.
        if not (args.src_file and args.tgt_file):
            raise SystemExit("--src-file and --tgt-file go together")
        train = None
        if comm.inter_rank == 0:
            from chainermn_tpu.datasets import train_bpe

            with open(args.src_file, encoding="utf-8") as f:
                src_lines = f.read().splitlines()
            with open(args.tgt_file, encoding="utf-8") as f:
                tgt_lines = f.read().splitlines()
            if len(src_lines) != len(tgt_lines):
                raise SystemExit(
                    f"parallel corpus length mismatch: {len(src_lines)} "
                    f"vs {len(tgt_lines)} lines")
            cache = args.src_file + f".bpe{args.bpe_vocab}.json"
            tok = train_bpe(src_lines + tgt_lines, args.bpe_vocab,
                            cache_path=cache)
            train = [(np.asarray(tok.encode(s), np.int32),
                      np.asarray(tok.encode(t), np.int32))
                     for s, t in zip(src_lines, tgt_lines)]
            vocab = tok.vocab_size
            print(f"corpus: {len(train)} pairs, BPE vocab {vocab} "
                  f"({cache})")
        vocab = comm.bcast_obj(vocab if comm.inter_rank == 0 else None)
    else:
        train = (synthetic_translation(args.n_train, src_vocab=args.vocab,
                                       tgt_vocab=args.vocab, seed=0)
                 if comm.inter_rank == 0 else None)
    train = chainermn_tpu.scatter_dataset(train, comm, shuffle=True, seed=0,
                                          shared_storage=False)

    model = Seq2Seq(n_layers=args.layer, n_units=args.unit,
                    src_vocab=vocab, tgt_vocab=vocab)

    sample = pad_batch([train[i] for i in range(2)], args.bucket)
    variables = model.init(jax.random.PRNGKey(0), *sample[:3])
    params = comm.bcast_data(variables["params"])

    optimizer = chainermn_tpu.create_multi_node_optimizer(
        optax.adam(1e-3), comm
    )
    opt_state = optimizer.init(params)

    mesh = comm.mesh
    axes = comm.axis_names
    dspec = P(axes if len(axes) > 1 else axes[0])
    dsh = NamedSharding(mesh, dspec)

    def local_step(state, src, src_len, tgt_in, tgt_out):
        params, opt_state = state

        def f(p):
            logits = model.apply({"params": p}, src, src_len, tgt_in)
            loss, _ = seq2seq_loss(logits, tgt_out)
            return loss

        loss, grads = jax.value_and_grad(f)(params)
        updates, new_opt = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return (params, new_opt), {
            "main/loss": jax.lax.pmean(loss, axes),
            "main/perp": jnp.exp(jax.lax.pmean(loss, axes)),
        }

    step = jax.jit(shard_map(
        local_step, mesh=mesh,
        in_specs=((P(), P()), dspec, dspec, dspec, dspec),
        out_specs=((P(), P()), P()),
    ))

    state = (params, opt_state)
    it = SerialIterator(train, args.batchsize, shuffle=True, seed=0)
    iteration = 0
    import time

    t0 = time.time()
    while it.epoch < args.epoch:
        batch = it.next()
        arrays = pad_batch(batch, args.bucket)
        arrays = tuple(jax.device_put(a, dsh) for a in arrays)
        state, metrics = step(state, *arrays)
        iteration += 1
        if comm.is_master and iteration % 8 == 0:
            print(f"epoch {it.epoch} iter {iteration} "
                  f"loss {float(metrics['main/loss']):.4f} "
                  f"perp {float(metrics['main/perp']):.1f} "
                  f"({time.time() - t0:.1f}s)", flush=True)
    if comm.is_master:
        print(f"final loss: {float(metrics['main/loss']):.4f}")

    # translate a few training pairs back (reference: the seq2seq example's
    # post-epoch translate check); --beam K switches greedy → beam search
    from chainermn_tpu.models.seq2seq import (
        beam_translate,
        corpus_bleu,
        greedy_translate,
        strip_special,
    )

    params = state[0]
    srcs, src_len, _, tgt_out = pad_batch(train[:4], args.bucket)
    if args.beam > 0:
        hyp = beam_translate(model, {"params": params}, srcs, src_len,
                             beam=args.beam, max_len=args.bucket)
    else:
        hyp = greedy_translate(model, {"params": params}, srcs, src_len,
                               max_len=args.bucket)
    hyp = np.asarray(hyp)
    if comm.is_master:
        refs = [strip_special(r) for r in tgt_out]
        hyps = [strip_special(h) for h in hyp]
        bleu = corpus_bleu(refs, hyps)
        mode = f"beam={args.beam}" if args.beam else "greedy"
        print(f"translate demo ({mode}): BLEU {bleu:.4f}")
        for i in range(2):
            print(f"  src {srcs[i][:8]}... -> hyp {hyp[i][:8]}...")
    return float(metrics["main/loss"])


if __name__ == "__main__":
    # supervisor exit-status contract (docs/fault_tolerance.md):
    # 0 clean, 143 preempted-and-checkpointed, 75 watchdog abort
    from chainermn_tpu.resilience.supervisor import main_exit_code
    sys.exit(main_exit_code(main))
