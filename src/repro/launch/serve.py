"""Serving driver: ``python -m repro.launch.serve --arch <id> [...]``.

Runs the COW-paged serving engine with batched requests against a
reduced (smoke) config on CPU hosts, or the full config on a TPU slice
(same code path the decode dry-run compiles).  ``--smc`` switches to
population-based decoding (N particles, zero-copy resampling forks).
"""

from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="musicgen_large")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--smc", action="store_true", help="population-based decoding")
    ap.add_argument("--particles", type=int, default=16)
    args = ap.parse_args()

    from repro import compile_cache

    compile_cache.enable()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config, smoke_config
    from repro.models.model import LanguageModel

    key = jax.random.PRNGKey(0)
    cfg = get_config(args.arch) if args.full else smoke_config(args.arch)
    lm = LanguageModel(cfg)
    params, _ = lm.init(key)
    max_len = args.prompt_len + args.steps + 16

    if args.smc:
        from repro.serving.smc_decode import SMCDecoder

        dec = SMCDecoder(lm, params, n_particles=args.particles, max_len=max_len)
        prompt = jax.random.randint(key, (args.prompt_len,), 0, cfg.vocab_size)
        t0 = time.time()
        res = dec.run(key, prompt, steps=args.steps)
        dt = time.time() - t0
        dense = dec.dense_equivalent_blocks(args.steps, args.prompt_len)
        peak = int(np.max(np.asarray(res.used_blocks_trace)))
        print(f"SMC decode: {args.particles} particles x {args.steps} tokens "
              f"in {dt:.1f}s; {int(res.resampled.sum())} zero-copy forks; "
              f"peak {peak} KV blocks vs {dense} dense ({dense / peak:.2f}x)")
        return

    from repro.serving.engine import ServeEngine

    eng = ServeEngine(lm, params, max_seqs=args.batch, max_len=max_len)
    prompts = jax.random.randint(
        key, (args.batch, args.prompt_len), 0, cfg.vocab_size
    )
    logits = eng.prefill(prompts, jnp.arange(args.batch, dtype=jnp.int32))
    tok = jnp.argmax(logits, -1)[:, None]
    outs = [tok]
    t0 = time.time()
    for _ in range(args.steps):
        logits = eng.decode(tok)
        tok = jnp.argmax(logits, -1)[:, None]
        outs.append(tok)
    dt = time.time() - t0
    toks = np.concatenate([np.asarray(t) for t in outs], axis=1)
    print(f"served {args.batch} requests x {args.steps} tokens "
          f"in {dt:.1f}s ({dt / args.steps * 1e3:.0f} ms/step); "
          f"{eng.used_blocks} KV blocks live")
    print("greedy continuations (first 12 tokens):")
    for row in toks[:, :12]:
        print("  ", row)


if __name__ == "__main__":
    main()
