"""Batched serving driver: prefill a prompt batch, decode N tokens, report
prefill latency / decode throughput.

  PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-7b --preset smoke \
      --batch 4 --prompt-len 64 --gen 32
"""
from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from repro.configs import get_arch
from repro.launch.platform import enable_compile_cache
from repro.launch.train import reduced_config
from repro.models import model_zoo
from repro.serving import ServeEngine


def main():
    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="llama3.2-1b")
    p.add_argument("--preset", default="smoke", choices=["smoke", "100m",
                                                         "full"])
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=64)
    p.add_argument("--gen", type=int, default=32)
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()

    spec = get_arch(args.arch)
    cfg = reduced_config(spec.model, args.preset)
    max_seq = args.prompt_len + args.gen + (
        cfg.vision_tokens if cfg.family == "vlm" else 0)
    model = model_zoo.build_model(cfg, max_seq=max_seq)
    params = model.init(jax.random.PRNGKey(args.seed))

    rng = np.random.RandomState(args.seed)
    batch = {"tokens": rng.randint(
        0, cfg.vocab_size, (args.batch, args.prompt_len)).astype(np.int32)}
    if cfg.family == "encdec":
        batch["enc_embeds"] = rng.randn(
            args.batch, cfg.encoder_seq, cfg.d_model).astype(np.float32) * .02
    if cfg.family == "vlm":
        batch["patch_embeds"] = rng.randn(
            args.batch, cfg.vision_tokens, cfg.d_model).astype(np.float32) * .02

    eng = ServeEngine(model, params, max_seq=max_seq, batch=args.batch,
                      temperature=args.temperature, seed=args.seed)
    res = eng.generate(batch, max_new_tokens=args.gen)
    print(json.dumps({
        "arch": args.arch, "preset": args.preset,
        "batch": args.batch, "prompt_len": args.prompt_len,
        "generated": int(res.tokens.shape[1] - args.prompt_len),
        "prefill_seconds": round(res.prefill_seconds, 4),
        "decode_seconds": round(res.decode_seconds, 4),
        "decode_tokens_per_s": round(res.decode_tokens_per_s, 1),
    }))


if __name__ == "__main__":
    main()
