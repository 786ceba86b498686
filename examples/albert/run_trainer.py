"""Collaborative ALBERT pretraining peer (capability parity: reference
examples/albert/run_trainer.py — the flagship recipe: every peer runs this script,
joins the swarm via the DHT, and trains one shared ALBERT with the collaborative
Optimizer; peers may come and go at any time).

Data: pass ``--dataset_path corpus.txt`` to train on a real local corpus (see
examples/albert/data.py — self-contained tokenizer, BERT-style 80/10/10 masking;
add ``--hf_tokenizer <name>`` to use an on-disk HuggingFace dataset + cached
tokenizer instead). Without it, synthetic MLM data keeps the recipe runnable
anywhere."""

from __future__ import annotations

import argparse
import time

import numpy as np


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--run_id", default="albert_demo")
    parser.add_argument("--model", choices=("albert", "causal"), default="albert",
                        help="albert: masked-LM flagship; causal: decoder-only "
                             "next-token pretraining (models/causal_lm.py)")
    parser.add_argument("--initial_peers", nargs="*", default=[])
    parser.add_argument("--target_batch_size", type=int, default=4096)
    parser.add_argument("--batch_size", type=int, default=16)
    parser.add_argument("--seq_len", type=int, default=128)
    parser.add_argument("--learning_rate", type=float, default=1e-3)
    parser.add_argument("--warmup_epochs", type=int, default=100)
    parser.add_argument("--total_epochs", type=int, default=10_000)
    parser.add_argument("--matchmaking_time", type=float, default=3.0)
    parser.add_argument("--max_steps", type=int, default=10**9)
    parser.add_argument("--client_mode", action="store_true")
    parser.add_argument("--tiny", action="store_true", help="albert-tiny config (CPU-friendly)")
    parser.add_argument("--powersgd_rank", type=int, default=0, help=">0: PowerSGD gradient compression")
    parser.add_argument("--dataset_path", default=None, help="local text corpus (or HF dataset dir with --hf_tokenizer)")
    parser.add_argument("--hf_tokenizer", default=None, help="cached HuggingFace tokenizer name for --dataset_path")
    parser.add_argument("--vocab_path", default=None,
                        help="shared vocab file for text corpora: ALL peers must use the same token "
                             "mapping (first peer writes it, the rest load it)")
    parser.add_argument("--seed", type=int, default=None, help="data sampling seed (default: random per peer)")
    parser.add_argument("--backup_every", type=int, default=30,
                        help="healthy steps between in-memory state backups for "
                             "NaN-restore (0 disables the guard; reference "
                             "run_trainer.py:62-130)")
    parser.add_argument("--metrics_jsonl", default=None,
                        help="append per-report metrics as JSON lines (wandb-style "
                             "key/value records, offline-friendly)")
    from hivemind_tpu.utils.platform import add_platform_arg, apply_platform

    add_platform_arg(parser)
    args = parser.parse_args()
    apply_platform(args)

    import jax
    import jax.numpy as jnp
    import optax

    from hivemind_tpu.dht import DHT
    from hivemind_tpu.models import AlbertConfig, AlbertForMaskedLM, make_mlm_loss_fn, make_synthetic_mlm_batch
    from hivemind_tpu.optim import Optimizer
    from hivemind_tpu.utils.logging import get_logger

    logger = get_logger("albert_trainer")

    dht = DHT(initial_peers=args.initial_peers, start=True)
    for maddr in dht.get_visible_maddrs():
        logger.info(f"to join this training run: --initial_peers {maddr}")

    if args.model == "causal":
        from hivemind_tpu.models import CausalLM, CausalLMConfig, causal_lm_loss

        config = (
            CausalLMConfig.tiny(max_position=args.seq_len) if args.tiny
            else CausalLMConfig.base(max_position=args.seq_len)
        )
        model = CausalLM(config)
        sample = make_synthetic_mlm_batch(jax.random.PRNGKey(0), config, args.batch_size, args.seq_len)
        params = model.init(jax.random.PRNGKey(0), sample["input_ids"][:1, :8])["params"]

        def loss_fn(params, batch):
            # the sampler's "labels" field is the UNMASKED token stream — exactly
            # what next-token prediction trains on
            tokens = batch["labels"]
            return causal_lm_loss(model.apply({"params": params}, tokens), tokens)
    else:
        config = AlbertConfig.tiny(max_position=args.seq_len) if args.tiny else AlbertConfig.base(max_position=args.seq_len)
        model = AlbertForMaskedLM(config)
        sample = make_synthetic_mlm_batch(jax.random.PRNGKey(0), config, args.batch_size, args.seq_len)
        params = model.init(jax.random.PRNGKey(0), sample["input_ids"][:1, :8])["params"]

        # masked-only loss: ~4x cheaper MLM head (same objective at 15% masking)
        loss_fn = make_mlm_loss_fn(model, masked_loss_fraction=0.25)

    @jax.jit
    def loss_and_grad(params, batch):
        return jax.value_and_grad(loss_fn)(params, batch)

    grad_averager_factory = None
    grad_averager_opts = {}
    if args.powersgd_rank > 0:
        from hivemind_tpu.optim import PowerSGDGradientAverager

        logger.info(f"using PowerSGD rank {args.powersgd_rank} gradient compression")
        grad_averager_factory = PowerSGDGradientAverager
        grad_averager_opts = {"averager_rank": args.powersgd_rank}
    # the reference ALBERT recipe trains with LAMB + linear warmup + clipping;
    # schedules are epoch-keyed (one optax update per virtual epoch)
    from hivemind_tpu.moe.server.layers import lamb_with_warmup

    opt = Optimizer(
        dht=dht,
        run_id=args.run_id,
        target_batch_size=args.target_batch_size,
        params=params,
        optimizer=lamb_with_warmup(args.learning_rate, args.warmup_epochs, args.total_epochs),
        batch_size_per_step=args.batch_size,
        matchmaking_time=args.matchmaking_time,
        client_mode=args.client_mode,
        grad_averager_factory=grad_averager_factory,
        grad_averager_opts=grad_averager_opts,
        verbose=True,
    )

    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from data import make_batch_sampler

    sample_batch = make_batch_sampler(
        config, args.seq_len, dataset_path=args.dataset_path,
        hf_tokenizer=args.hf_tokenizer, vocab_path=args.vocab_path,
        seed=args.seed if args.seed is not None else int(time.time() * 1000) % 2**31,
    )
    from hivemind_tpu.optim import NaNGuard
    from hivemind_tpu.utils.profiling import JsonlMetricsSink

    guard = NaNGuard(opt, backup_every=args.backup_every) if args.backup_every > 0 else None
    metrics_sink = JsonlMetricsSink(args.metrics_jsonl)

    step = 0
    loss_ema = None
    while step < args.max_steps:
        batch = {k: jnp.asarray(v) for k, v in sample_batch(args.batch_size).items()}
        loss, grads = loss_and_grad(opt.params, batch)
        loss_value = float(loss)
        if guard is not None:
            guard.step(loss_value, grads)  # restores the backup on NaN/Inf
        else:
            opt.step(grads)
        if np.isfinite(loss_value):
            loss_ema = loss_value if loss_ema is None else 0.95 * loss_ema + 0.05 * loss_value
        step += 1
        if step % 10 == 0:
            progress = opt.tracker.global_progress
            ema_text = f"{loss_ema:.4f}" if loss_ema is not None else "n/a"
            logger.info(
                f"step {step} epoch {opt.local_epoch} loss {ema_text} "
                f"(swarm: {progress.num_peers} peers, {progress.samples_accumulated}/"
                f"{args.target_batch_size} samples)"
                + (f" [{guard.restores} NaN restores]" if guard is not None and guard.restores else "")
            )
            metrics_sink.log({
                "step": step, "epoch": opt.local_epoch, "loss": loss_value,
                "loss_ema": loss_ema, "num_peers": progress.num_peers,
                "samples_accumulated": progress.samples_accumulated,
                "time": time.time(),
            })

    # reached max_steps (smoke runs): leave the swarm cleanly so the
    # process actually exits instead of hanging on background threads
    final_text = f"{loss_ema:.4f}" if loss_ema is not None else "n/a"
    logger.info(f"training finished after {step} steps at epoch {opt.local_epoch}, final loss {final_text}")
    metrics_sink.close()
    opt.shutdown()
    dht.shutdown()


if __name__ == "__main__":
    main()
