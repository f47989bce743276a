"""Serving launcher: batched greedy/temperature decode through the engine.

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
        --slots 4 --tokens 64 --smoke

``--paged`` switches to continuous batching over the paged KV cache
(docs/serving.md): requests with RAGGED prompt lengths stream through the
slots, retiring on completion and admitting queued work mid-flight.

    PYTHONPATH=src python -m repro.launch.serve --smoke --paged \
        --requests 16 --tokens 32
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import get_config
from repro.configs.reduce import smoke_config
from repro.models.api import model_api
from repro.serving import ServingEngine
from repro.runtime.compile_cache import use_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=64)
    ap.add_argument("--max-len", type=int, default=512)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--backend", default=None,
                    help="attention backend: jnp | pallas | interpret | auto "
                         "| any registered plug-in (default: config)")
    ap.add_argument("--paged", action="store_true",
                    help="continuous batching over the paged KV cache")
    ap.add_argument("--requests", type=int, default=8,
                    help="(--paged) number of ragged requests to serve")
    ap.add_argument("--page", type=int, default=None,
                    help="(--paged) tokens per KV block (default: lcm of "
                         "local window and compression block)")
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="(--paged) KV pool size in blocks")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="(--paged) disable cross-request prefix reuse")
    args = ap.parse_args()
    use_compile_cache()

    mcfg = get_config(args.arch)
    if args.smoke:
        mcfg = smoke_config(mcfg)
    if args.backend:
        import dataclasses
        mcfg = mcfg.scaled(bsa=dataclasses.replace(mcfg.bsa, backend=args.backend))
    api = model_api(mcfg)
    params = api.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    if args.paged:
        eng = ServingEngine(api, params, batch_slots=args.slots,
                            max_len=args.max_len,
                            temperature=args.temperature, paged=True,
                            page=args.page, num_blocks=args.num_blocks,
                            prefix_cache=not args.no_prefix_cache)
        lens = rng.integers(args.prompt_len // 2, args.prompt_len + 1,
                            args.requests)
        prompts = [rng.integers(0, mcfg.vocab_size, n, dtype=np.int32)
                   for n in lens]
        out = eng.serve(prompts, max_new_tokens=args.tokens)
        print(f"served {len(out)} requests in {eng.serve_steps} steps "
              f"(prompt lens {lens.min()}..{lens.max()}), throughput "
              f"{eng.tokens_per_second:.1f} tok/s, prefix blocks reused "
              f"{eng.kv.blocks_reused}, cow copies {eng.kv.cow_copies}")
        return
    eng = ServingEngine(api, params, batch_slots=args.slots,
                        max_len=args.max_len, temperature=args.temperature)
    prompts = rng.integers(0, mcfg.vocab_size, (args.slots, args.prompt_len),
                           dtype=np.int32)
    out = eng.generate(prompts, args.tokens)
    print("generated", out.shape, "throughput",
          f"{eng.tokens_per_second:.1f} tok/s")


if __name__ == "__main__":
    main()
