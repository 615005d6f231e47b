"""Serving driver: batched prefill + decode on the consensus parameters.

The paper's protocol output is the averaged shared parameters s-bar; serving
consumes a consensus checkpoint (or fresh init for demos) and runs
prefill + autoregressive decode with the KV/SSM caches, batch-sharded over
the mesh (on this CPU container: reduced configs, 1 device).

The serving plumbing — jitted prefill, rebuilding the cache at
prompt+gen capacity with the prompt prefix grafted in, and the
scan-compiled ``repro.engine.run_decode`` generation (one dispatch for the
whole generation) — lives in ``Session.serve`` (:mod:`repro.api`); this
driver only assembles the model, inputs and checkpoint.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --reduced \
        --batch 4 --prompt-len 32 --gen 16
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro.api import Session
from repro.checkpoint import load_checkpoint
from repro.configs import ARCH_NAMES, get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import Transformer


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ARCH_NAMES, default="llama3.2-1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    arch = get_config(args.arch)
    cfg = arch.smoke if args.reduced else arch.model
    model = Transformer(cfg)
    key = jax.random.PRNGKey(args.seed)
    params = model.init(key)
    if args.checkpoint:
        params, meta = load_checkpoint(args.checkpoint, params)
        print(f"restored checkpoint (step {meta['step']})")

    # serve-only session: no topology, no protocol — just the model front
    # door (the same Session.serve a training session exposes post-run)
    session = Session.build(model=model, key=key)

    b, s = args.batch, args.prompt_len
    if cfg.input_mode == "embeddings":
        batch = {"embeds": jax.random.normal(key, (b, s, cfg.d_model)) * 0.1,
                 "labels": jnp.zeros((b, s), jnp.int32)}
    else:
        batch = {"tokens": jax.random.randint(key, (b, s), 0, cfg.vocab_size)}
    enc = None
    if arch.family == "vlm":
        n_img = cfg.groups[0].n_image_tokens
        enc = jax.random.normal(key, (b, n_img, cfg.d_model)) * 0.1
        batch["image_embeds"] = enc
    step_inputs = None
    if cfg.input_mode == "embeddings" and args.gen > 1:
        step_inputs = jax.random.normal(
            jax.random.fold_in(key, 7), (args.gen - 1, b, cfg.d_model)) * 0.1

    report = session.serve(params, batch, gen=args.gen,
                           temperature=args.temperature, key=key, enc=enc,
                           step_inputs=step_inputs)
    print(f"prefill: {report.prefill_s:.2f}s")
    print(f"decode: {report.steps} steps in {report.decode_s:.2f}s "
          f"({report.ms_per_token:.1f} ms/token/batch, scan engine)")
    print("generated token ids (first sequence):", report.tokens[0].tolist())


if __name__ == "__main__":
    main()
