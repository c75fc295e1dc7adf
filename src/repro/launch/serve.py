"""Batched serving driver: prefill + decode with a Mez-fed request stream.

Serves a (reduced-on-CPU) model with batched requests: prompts are prefilled
once, then decode steps generate tokens for the whole batch.  Demonstrates
the serving-side runtime the decode_* dry-run cells lower:

  * preallocated KV cache with slack, length-masked decode
  * per-step latency tracking (p50/p95) and tokens/sec
  * optional Mez ingestion: a camera topic is subscribed with
    (latency, accuracy) bounds and delivered frames are batched into
    patch embeddings for the VLM family (the end-to-end IoT-Edge loop).

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b \
      --batch 4 --prompt-len 64 --gen 32
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.models.registry import DECODE_SLACK, build_model, make_batch

__all__ = ["serve"]


def serve(arch: str, *, batch: int = 4, prompt_len: int = 64, gen: int = 32,
          reduced: bool = True, seed: int = 0,
          temperature: float = 0.0) -> dict:
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    key = jax.random.PRNGKey(seed)
    params = model.init_params(key)

    pb = make_batch(cfg, batch, prompt_len, train=False, key=key)
    kw = {"enc_len": prompt_len} if cfg.family == "audio" else {}
    cache = model.init_cache(batch, prompt_len + gen + DECODE_SLACK, **kw)

    # mezlint: disable=MZ02 -- jitted once per serve process, reused every token
    prefill = jax.jit(model.prefill)
    # mezlint: disable=MZ02 -- same: one wrapper per process
    decode = jax.jit(model.decode_step, donate_argnums=(2,))

    t0 = time.monotonic()
    logits, cache = jax.block_until_ready(prefill(params, pb, cache))
    t_prefill = time.monotonic() - t0

    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    lat = []
    out_tokens = [np.asarray(tok)]
    for i in range(gen):
        t0 = time.monotonic()
        logits, cache = jax.block_until_ready(decode(params, tok, cache))
        lat.append(time.monotonic() - t0)
        if temperature > 0:
            key, sub = jax.random.split(key)
            tok = jax.random.categorical(
                sub, logits[:, -1] / temperature)[:, None].astype(jnp.int32)
        else:
            tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        out_tokens.append(np.asarray(tok))
    lat = np.asarray(lat)
    toks = np.concatenate(out_tokens, axis=1)
    assert int(toks.max()) < cfg.vocab_size, "padded-vocab token leaked"
    return {
        "prefill_s": t_prefill,
        "decode_p50_ms": float(np.percentile(lat, 50) * 1e3) if len(lat) else 0,
        "decode_p95_ms": float(np.percentile(lat, 95) * 1e3) if len(lat) else 0,
        "tokens_per_s": float(batch * len(lat) / lat.sum()) if len(lat) else 0,
        "tokens": toks,
        "cache_len": int(cache.length),
    }


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()
    out = serve(args.arch, batch=args.batch, prompt_len=args.prompt_len,
                gen=args.gen, temperature=args.temperature)
    print(f"[serve] prefill {out['prefill_s']*1e3:.1f} ms; decode p50 "
          f"{out['decode_p50_ms']:.2f} ms p95 {out['decode_p95_ms']:.2f} ms; "
          f"{out['tokens_per_s']:.1f} tok/s; cache_len={out['cache_len']}")


if __name__ == "__main__":
    main()
