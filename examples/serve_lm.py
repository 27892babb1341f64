"""Serve a small LM with batched greedy decoding over a KV cache — the
serve_step path that the decode_32k / long_500k dry-run cells lower.

    PYTHONPATH=src python examples/serve_lm.py --batch 8 --new-tokens 64
"""

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.models import build_model
from repro.utils.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=64)
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced(vocab_size=512)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    rng = jax.random.key(1)

    prompt = jax.random.randint(rng, (args.batch, args.prompt_len), 0,
                                cfg.vocab_size)
    max_len = args.prompt_len + args.new_tokens + 1
    batch = {"tokens": prompt}
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(
            rng, (args.batch, cfg.encoder_seq_len, cfg.d_model), jnp.float32)
    cache = model.decode_init(params, batch, max_len, dtype=jnp.float32)

    step = jax.jit(model.decode_step)

    # prefill by teacher-forcing the prompt through the decode path
    tok = prompt[:, 0]
    for t in range(args.prompt_len):
        logits, cache = step(params, cache, prompt[:, t])
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    out = [tok]
    t0 = time.perf_counter()
    for _ in range(args.new_tokens):
        logits, cache = step(params, cache, tok)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out.append(tok)
    jax.block_until_ready(tok)
    dt = time.perf_counter() - t0
    total = args.new_tokens * args.batch
    print(f"{args.arch} (reduced): {total} tokens in {dt:.2f}s "
          f"-> {total/dt:.1f} tok/s (batch={args.batch})")
    print("sample:", jnp.stack(out, axis=1)[0][:16].tolist())


if __name__ == "__main__":
    enable_compile_cache()
    main()
