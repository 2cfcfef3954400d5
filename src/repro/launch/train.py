"""Training launcher — the end-to-end driver.

Runs real training on whatever devices exist (CPU here; the same code
pjit-distributes on a pod via make_production_mesh), with the full
production stack: sharded params/optimizer, three-stage MUX training,
async checkpointing, fault-tolerant supervisor, straggler detection.

Examples:
    # train a ~100M-param MUX-BERT on synthetic corpus for 300 steps
    python -m repro.launch.train --model mux-bert-base --mux-n 2 \
        --steps 300 --batch 32 --seq 128 --ckpt /tmp/ckpt

    # reduced assigned-arch config end-to-end
    python -m repro.launch.train --arch gemma-2b --reduced --steps 50
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import MuxSpec
from repro.configs import get_config, model_kind
from repro.data import MarkovCorpus, ShardedLoader
from repro.models import TransformerLM, MuxBERT, bert_config
from repro.models.config import param_count
from repro.optim import AdamW, linear_warmup_cosine_decay
from repro.train import make_train_step, jit_step, causal_lm_loss
from repro.train.mux_stages import retrieval_stage, mlm_stage
from repro.checkpoint import AsyncCheckpointManager
from repro.runtime import Supervisor, StragglerDetector
from repro.launch.compile_cache import enable_compile_cache


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default=None,
                    help="mux-bert-{small,base,large} | mux-electra-base")
    ap.add_argument("--arch", default=None, help="assigned arch id")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--mux-n", type=int, default=2)
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--warmup-steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--xla-async", action="store_true",
                    help="enable async collectives (TPU runtime flags)")
    args = ap.parse_args(argv)

    mux = MuxSpec(n=args.mux_n)
    key = jax.random.PRNGKey(args.seed)

    if args.arch:
        cfg = get_config(args.arch, reduced=args.reduced)
        params = TransformerLM.init(key, cfg, mux)

        def loss_fn(p, batch, rng):
            out = TransformerLM.apply(p, cfg, batch["tokens"], mux=mux,
                                      dtype=jnp.float32)
            loss = causal_lm_loss(out["logits"], batch["tokens"])
            if cfg.moe is not None:
                loss = loss + cfg.moe.router_aux_weight * out["aux"]
            return loss, {}
        stages = [("lm", loss_fn, args.steps)]
    else:
        name = args.model or "mux-bert-base"
        size = name.split("-")[-1]
        cfg = bert_config(size, vocab_size=args.vocab,
                          max_seq_len=args.seq)
        params = MuxBERT.init(key, cfg, mux,
                              electra="electra" in name)
        stages = [
            ("retrieval-warmup", retrieval_stage(cfg, mux),
             args.warmup_steps),
            ("mlm-pretrain", mlm_stage(cfg, mux), args.steps),
        ]

    print(f"model: {cfg.name}  params={param_count(cfg)/1e6:.1f}M  "
          f"mux N={mux.n}  devices={len(jax.devices())}")

    opt = AdamW(lr=linear_warmup_cosine_decay(
        args.lr, max(args.steps // 10, 10), args.steps))
    opt_state = opt.init(params)

    corpus = MarkovCorpus(vocab_size=cfg.vocab_size, seed=args.seed)
    loader = ShardedLoader(
        lambda rng, b, l: {"tokens": corpus.sample(rng, b, l)},
        args.batch, args.seq, seed=args.seed)

    ckpt = AsyncCheckpointManager(args.ckpt or "/tmp/repro_ckpt", keep_k=3)

    for stage_name, loss_fn, n_steps in stages:
        print(f"--- stage: {stage_name} ({n_steps} steps) ---")
        step = jit_step(make_train_step(loss_fn, opt), donate=False)

        def step_wrap(state, batch, i):
            p, o = state
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            p, o, m = step(p, o, batch, jax.random.fold_in(key, i))
            return (p, o), m

        sup = Supervisor(step_fn=step_wrap, ckpt=ckpt,
                         checkpoint_every=max(n_steps // 3, 20),
                         straggler=StragglerDetector())
        t0 = time.time()
        (params, opt_state), hist = sup.run((params, opt_state),
                                            iter(loader), n_steps)
        metrics = [h for h in hist if "loss" in h]
        dt = time.time() - t0
        if metrics:
            print(f"    steps={len(metrics)}  "
                  f"loss {float(metrics[0]['loss']):.4f} -> "
                  f"{float(metrics[-1]['loss']):.4f}  "
                  f"({dt:.0f}s, {1000*dt/max(len(metrics),1):.0f} ms/step,"
                  f" stragglers={len(sup.straggler.events)})")
    ckpt.wait()
    print("done.")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
