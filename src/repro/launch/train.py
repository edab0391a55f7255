"""End-to-end training driver (deliverable (b)).

Runs real steps on the host device(s): synthetic deterministic data,
AdamW + schedule, periodic async checkpoints with auto-resume, throughput
logging.  ``--preset smoke`` shrinks any assigned arch to a CPU-runnable
config; ``--preset 100m`` is the ~100M-param end-to-end run.

  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b \
      --preset 100m --steps 300 --batch 8 --seq 512
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import AsyncCheckpointer
from repro.configs import get_arch
from repro.configs.base import ShapeConfig, TrainConfig
from repro.data.prefetch import Prefetcher
from repro.data.synthetic import SyntheticTokenStream
from repro.launch.platform import enable_compile_cache
from repro.models import model_zoo
from repro.training import TrainState, make_train_state, make_train_step

PRESETS = {
    # (layers, d_model, heads, kv, d_ff, vocab)
    "smoke": dict(num_layers=2, d_model=128, num_heads=4, num_kv_heads=2,
                  d_ff=256, vocab_size=2048),
    "100m": dict(num_layers=12, d_model=768, num_heads=12, num_kv_heads=4,
                 d_ff=2048, vocab_size=32768),
}


def reduced_config(cfg, preset: str):
    if preset == "full":
        return cfg
    ov = dict(PRESETS[preset])
    ov["dtype"] = "float32"
    if cfg.family == "moe":
        ov.update(moe_num_experts=8, moe_top_k=2, moe_group_size=256,
                  moe_shared_d_ff=512)
    if cfg.family == "hybrid":
        ov.update(num_layers=8, mamba_head_dim=32, mamba_d_state=8,
                  moe_num_experts=4, moe_top_k=2, moe_group_size=256)
    if cfg.family == "rwkv6":
        d = ov["d_model"]
        ov.update(rwkv_head_dim=32, num_heads=d // 32, num_kv_heads=d // 32,
                  rwkv_lora_rank=16, rwkv_decay_lora_rank=16)
    if cfg.family == "encdec":
        ov.update(encoder_layers=2, encoder_seq=96, rope_theta=0.0)
    if cfg.family == "vlm":
        ov.update(vision_tokens=16)
    return cfg.replace(**ov)


def main():
    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="llama3.2-1b")
    p.add_argument("--preset", default="smoke",
                   choices=["smoke", "100m", "full"])
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=256)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--ckpt-every", type=int, default=100)
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--resume", action="store_true")
    args = p.parse_args()

    spec = get_arch(args.arch)
    cfg = reduced_config(spec.model, args.preset)
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    train_cfg = TrainConfig(
        learning_rate=args.lr, warmup_steps=min(50, args.steps // 10 + 1),
        decay_steps=args.steps, schedule=spec.train.schedule,
        stable_steps=spec.train.stable_steps)

    model = model_zoo.build_model(cfg, max_seq=args.seq)
    n_params = model_zoo.count_params(cfg, max_seq=args.seq)
    print(f"arch={args.arch} preset={args.preset} params={n_params/1e6:.1f}M")

    rng = jax.random.PRNGKey(args.seed)
    params = model.init(rng)
    state = make_train_state(params, train_cfg)
    step_fn = jax.jit(make_train_step(model_zoo.make_loss_fn(model),
                                      train_cfg), donate_argnums=(0,))

    ckpt = None
    start_step = 0
    if args.ckpt_dir:
        ckpt = AsyncCheckpointer(args.ckpt_dir)
        if args.resume:
            snap = ckpt.restore_latest()
            if snap is not None:
                state = jax.tree.map(jnp.asarray, snap["tree"])
                start_step = snap["step"]
                print(f"resumed at step {start_step}")

    stream = SyntheticTokenStream(cfg, shape, seed=args.seed, step=start_step)
    it = Prefetcher(stream, depth=2)
    t0 = time.time()
    tokens_seen = 0
    for i in range(start_step, args.steps):
        batch = {k: jnp.asarray(v) for k, v in next(it).items()}
        state, metrics = step_fn(state, batch)
        tokens_seen += args.batch * args.seq
        if (i + 1) % args.log_every == 0 or i + 1 == args.steps:
            loss = float(metrics["loss"])
            dt = time.time() - t0
            print(f"step {i+1:5d} loss={loss:.4f} "
                  f"lr={float(metrics['lr']):.2e} "
                  f"gnorm={float(metrics['grad_norm']):.2f} "
                  f"tok/s={tokens_seen/dt:,.0f}", flush=True)
        if ckpt and (i + 1) % args.ckpt_every == 0:
            ckpt.save(i + 1, state)
    if ckpt:
        ckpt.save(args.steps, state)
        ckpt.wait()
    it.close()
    print(json.dumps({"final_loss": float(metrics["loss"]),
                      "steps": args.steps,
                      "tokens_per_second": tokens_seen / (time.time() - t0)}))


if __name__ == "__main__":
    main()
