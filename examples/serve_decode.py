"""Lockstep-batch serving reference driver: one batched prefill → decode
with a standing KV cache, every sequence at the same depth, dispatched on
profiled queues (prefill and decode get separate lanes, so the profiler
shows their interleaving — the paper's two-queue pattern applied to
inference).

This is the *reference* path: simplest possible batching, scalar decode
position, useful as the oracle the continuous-batching engine is tested
against.  For mixed-depth traffic — requests that arrive, progress, and
finish independently — use ``serve_engine.py``, which admits requests
into free slots of the standing cache and decodes all of them per tick
at per-sequence ring positions.

Run:  PYTHONPATH=src python examples/serve_decode.py --tokens 24
"""

import argparse

import jax
import jax.numpy as jnp

from repro.configs import get_smoke_config
from repro.core import Context, DispatchQueue
from repro.core.compile_cache import use_compile_cache
from repro.models.model import init_params
from repro.prof import Prof, queue_chart
from repro.serve.step import (DECODE_EVENT, PREFILL_EVENT,
                              align_prefill_cache, make_decode_step,
                              make_prefill_step)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mixtral-8x7b",
                    help="architecture id (smoke config is used)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--tokens", type=int, default=24)
    ap.add_argument("--attn-impl", default="xla", choices=["xla", "pallas"],
                    help="decode path: jnp reference or fused Pallas kernel"
                         " (mixed-depth traffic: see serve_engine.py)")
    args = ap.parse_args()

    use_compile_cache()
    import dataclasses
    cfg = dataclasses.replace(get_smoke_config(args.arch),
                              attn_impl=args.attn_impl)
    ctx = Context.new_accel()
    q_prefill = DispatchQueue(ctx, "Prefill")
    q_decode = DispatchQueue(ctx, "Decode")

    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    prompts = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                 cfg.vocab)
    ctx_embed = None
    if cfg.encoder_layers:
        ctx_embed = jax.random.normal(
            key, (args.batch, cfg.encoder_seq, cfg.d_model))
    elif cfg.vis_tokens:
        ctx_embed = jax.random.normal(
            key, (args.batch, cfg.vis_tokens, cfg.d_model))

    # factories return cached jitted steps — rebuilding them is free
    prefill = make_prefill_step(cfg)
    decode = make_decode_step(cfg)

    prof = Prof()
    prof.start()
    if ctx_embed is not None:
        logits, cache = q_prefill.enqueue(prefill, params, prompts, ctx_embed,
                                          name=PREFILL_EVENT)
    else:
        logits, cache = q_prefill.enqueue(prefill, params, prompts,
                                          name=PREFILL_EVENT)
    q_prefill.finish()
    cache = align_prefill_cache(cfg, cache, args.prompt_len,
                                target_len=args.prompt_len + args.tokens)

    tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
    generated = [tok]
    for i in range(args.tokens - 1):
        pos = jnp.int32(args.prompt_len + i)
        logits, cache = q_decode.enqueue(decode, params, cache, tok, pos,
                                         name=DECODE_EVENT,
                                         command_type=DECODE_EVENT)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        generated.append(tok)
    q_decode.finish()
    prof.stop()

    out = jnp.concatenate(generated, axis=1)
    print(f"generated {out.shape} tokens; first row: {out[0][:12].tolist()}")

    prof.add_queue("Prefill", q_prefill)
    prof.add_queue("Decode", q_decode)
    prof.calc()
    print(prof.get_summary())
    print(queue_chart(prof, width=80))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
