"""Serve a reduced LM with batched requests: prefill builds the KV cache,
then batched greedy decode — the serve_step path the decode_32k/long_500k
dry-run cells lower, exercised with real numbers on CPU.  Uses the flash-
decode Pallas kernel (interpret mode) for the attention-vs-cache hot spot and
cross-checks it against the model's own decode path.

Run:  PYTHONPATH=src python examples/lm_decode_serve.py --tokens 16
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.kernels.decode_attn.ops import flash_decode, flash_decode_ref
from repro.models import lm


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-14b-smoke")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=16)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    key = jax.random.PRNGKey(0)
    params = lm.init_model(cfg, key)
    B, S = args.batch, args.prompt_len
    max_seq = S + args.tokens + 1
    prompts = jax.random.randint(key, (B, S), 0, cfg.vocab_size)

    print(f"== prefill {B} requests x {S} tokens ({cfg.name}) ==")
    cache = lm.init_cache(cfg, B, max_seq=max_seq)
    t0 = time.perf_counter()
    logits, cache = lm.forward(params, {"tokens": prompts}, cfg,
                               mode="prefill", cache=cache)
    print(f"prefill: {(time.perf_counter()-t0)*1e3:.0f} ms "
          f"({B*S} tokens)")

    print(f"== batched greedy decode of {args.tokens} tokens ==")
    step = jax.jit(lambda p, c, t: lm.forward(p, {"tokens": t}, cfg,
                                              mode="decode", cache=c))
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    outs = [tok]
    t0 = time.perf_counter()
    for _ in range(args.tokens):
        logits, cache = step(params, cache, tok)
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        outs.append(tok)
    dt = time.perf_counter() - t0
    gen = np.concatenate([np.asarray(t) for t in outs], axis=1)
    print(f"decode: {dt/args.tokens*1e3:.1f} ms/token/batch "
          f"({B*args.tokens/dt:.0f} tok/s aggregate)")
    for b in range(min(B, 2)):
        print(f"  request {b}: {gen[b].tolist()}")

    print("== flash-decode kernel cross-check on the live cache ==")
    blk = cache["stacks"][0]["0_attn"]
    ck, cv = np.asarray(blk["k"][0]), np.asarray(blk["v"][0])
    hd = cfg.resolved_head_dim
    q = jax.random.normal(key, (B, 1, cfg.n_kv_heads, cfg.q_groups, hd))
    lens = np.full(B, int(cache["pos"]), np.int32)
    got = flash_decode(q, ck, cv, lens, block_s=32)
    exp = flash_decode_ref(q, ck, cv, lens)
    print(f"kernel vs oracle max|err|: "
          f"{float(jnp.max(jnp.abs(got-exp))):.2e}")


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    main()
