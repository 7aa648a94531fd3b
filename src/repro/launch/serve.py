"""Batched serving driver: chunked-prefill a prompt batch in one jitted
dispatch per chunk, then decode N tokens per request against KV/state
caches (ring-buffer window optional).

  PYTHONPATH=src python -m repro.launch.serve --arch xlstm-125m \
      --batch 4 --prompt-len 64 --decode 32

Pass ``--no-reduced`` to run the full-size architecture; ``--spec-k K
--draft-layers D`` adds a greedy speculative pass (truncated-depth
draft proposes K tokens/step, the target verifies the whole chunk in
one prefill dispatch) and checks it emits the identical token stream.
The multi-model request path (routing, group-by-model continuous
batching, per-cluster drafts, paged int8 pools) lives in
``repro.serve.gateway``.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch, reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import make_prefill_step, make_serve_step
from repro.models import transformer as tf
from repro.serve.draft import draft_config, truncate_lm_params


def chunked_prefill(prefill, params, caches, prompts, chunk: int):
    """Drive a ``make_prefill_step`` step over a (B, P) prompt batch in
    fixed-shape chunks (ragged tail padded + masked via n_valid).
    Returns (last-token logits (B, V), caches)."""
    B, P = prompts.shape
    logits = None
    for s in range(0, P, chunk):
        part = prompts[:, s:s + chunk]
        nv = part.shape[1]
        if nv < chunk:
            part = jnp.pad(part, ((0, 0), (0, chunk - nv)))
        logits, caches = prefill(params, caches, part,
                                 jnp.asarray(nv, jnp.int32))
    return logits, caches


def spec_decode(cfg, params, caches, dcfg, dparams, dcaches, first_tok,
                decode: int, k: int, window: int = 0):
    """Greedy speculative loop over a (B,) batch: draft proposes ``k``
    tokens per lane, the target verifies the [cur, d_1..d_k] chunk in
    ONE prefill dispatch, and the batch advances by the MINIMUM lane
    acceptance (``lm_spec_verify``'s shared n_keep — the single-model
    driver's simplification; the gateway vmaps per-lane). Returns the
    (B, >=decode) emitted token matrix plus (proposed, accepted)."""
    B = first_tok.shape[0]
    propose = jax.jit(
        lambda p, prev, pk, cur, cs: tf.lm_spec_propose(
            dcfg, p, prev, pk, cur, k, cs, window=window),
        donate_argnums=(4,), static_argnums=())
    verify = jax.jit(
        lambda p, chunk, dr, cs: tf.lm_spec_verify(
            cfg, p, chunk, dr, cs, window=window),
        donate_argnums=(3,))
    prev = jnp.zeros((B, k + 1), jnp.int32)
    keep = jnp.asarray(0, jnp.int32)
    cur = first_tok
    emitted, proposed, accepted = [], 0, 0
    n_out = 0
    while n_out < decode:
        props, dcaches = propose(dparams, prev, keep, cur, dcaches)
        chunk = jnp.concatenate([cur, props], axis=1)
        out, nk, caches = verify(params, chunk, props, caches)
        nk_h = int(nk)
        proposed += k
        accepted += nk_h - 1
        emitted.append(np.asarray(out[:, :nk_h]))
        n_out += nk_h
        prev, keep = chunk, nk
        cur = out[:, nk_h - 1][:, None]
    return np.concatenate(emitted, axis=1), proposed, accepted


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="xlstm-125m")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="shrink the architecture (--no-reduced for full)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--decode", type=int, default=32)
    ap.add_argument("--chunk", type=int, default=32,
                    help="prefill chunk length (one dispatch per chunk)")
    ap.add_argument("--window", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative tokens per step (0 = off)")
    ap.add_argument("--draft-layers", type=int, default=1,
                    help="draft depth for --spec-k (truncated target)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    if cfg.family == "audio":
        raise ValueError("use whisper driver paths in examples/")
    chunk = min(args.chunk, args.window) if args.window else args.chunk
    key = jax.random.PRNGKey(args.seed)
    params = tf.init_lm(cfg, key)
    max_len = args.prompt_len + args.decode
    caches = tf.init_lm_caches(cfg, args.batch, max_len, window=args.window)
    prefill = jax.jit(make_prefill_step(cfg, window=args.window),
                      donate_argnums=(1,))
    step = jax.jit(make_serve_step(cfg, window=args.window),
                   donate_argnums=(1,))

    prompts = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)
    t0 = time.time()
    logits, caches = chunked_prefill(prefill, params, caches, prompts, chunk)
    jax.block_until_ready(logits)
    prefill_s = time.time() - t0
    logits0 = logits

    tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
    out = [tok]
    t0 = time.time()
    for _ in range(args.decode):
        logits, caches = step(params, caches, tok)
        tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        out.append(tok)
    jax.block_until_ready(logits)
    decode_s = time.time() - t0
    toks = args.batch * args.decode
    print(f"arch={cfg.name} batch={args.batch} prompt={args.prompt_len} "
          f"decode={args.decode} chunk={chunk} window={args.window}")
    print(f"prefill: {prefill_s:.2f}s  decode: {decode_s:.2f}s "
          f"({toks / max(decode_s, 1e-9):.1f} tok/s)")
    seq = jnp.concatenate(out, axis=1)
    print("sample token ids:", np.asarray(seq[0])[:16].tolist())

    if args.spec_k:
        k = args.spec_k
        dcfg = draft_config(cfg, args.draft_layers)
        dparams = truncate_lm_params(cfg, dcfg, params)
        # headroom: each verify round writes a full k+1 chunk, so the
        # last round may run past prompt+decode
        scaches = tf.init_lm_caches(cfg, args.batch, max_len + k + 1,
                                    window=args.window)
        dcaches = tf.init_lm_caches(dcfg, args.batch, max_len + k + 1,
                                    window=args.window)
        _, scaches = chunked_prefill(prefill, params, scaches, prompts,
                                     chunk)
        dprefill = jax.jit(make_prefill_step(dcfg, window=args.window),
                           donate_argnums=(1,))
        _, dcaches = chunked_prefill(dprefill, dparams, dcaches, prompts,
                                     chunk)
        first = jnp.argmax(logits0, axis=-1)[:, None].astype(jnp.int32)
        t0 = time.time()
        spec, proposed, accepted = spec_decode(
            cfg, params, scaches, dcfg, dparams, dcaches, first,
            args.decode, k, window=args.window)
        spec_s = time.time() - t0
        spec_seq = np.concatenate([np.asarray(first), spec], axis=1)
        match = bool(np.array_equal(spec_seq[:, :args.decode + 1],
                                    np.asarray(seq)))
        rate = accepted / max(proposed, 1)
        print(f"spec: k={k} draft_layers={dcfg.n_layers} "
              f"{spec_s:.2f}s ({toks / max(spec_s, 1e-9):.1f} tok/s) "
              f"acceptance={rate:.3f} match_vanilla={match}")
        if not match:
            raise SystemExit("speculative stream diverged from vanilla")


if __name__ == "__main__":
    main()
