"""Serving launcher: batched prefill+decode with columnar output logging.

Generations are variable-length nested data ({request_id, prompt_len,
tokens[]}) and are written through the ParallelWriter — the inference-side
instance of the paper's technique (concurrent decode workers, one output
file).

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --smoke \
      --requests 8 --max-new 32 --out /tmp/gen.rntj
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, get_arch, smoke_config
from repro.core import Collection, ColumnBatch, Leaf, ParallelWriter, Schema
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.models.registry import build

GEN_SCHEMA = Schema([
    Leaf("request_id", "int64"),
    Leaf("prompt_len", "int32"),
    Collection("tokens", Leaf("_0", "int32")),
])


def generate(bundle, params, prompts: np.ndarray, max_new: int):
    """Greedy decode a batch of same-length prompts -> (B, max_new)."""
    b, s = prompts.shape[:2]
    max_len = s + max_new
    logits, cache = jax.jit(
        lambda p, t: bundle.prefill(p, t, max_len=max_len))(params, prompts)
    step = jax.jit(bundle.decode_step)
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    out = [tok]
    for i in range(max_new - 1):
        pos = jnp.full((b,), s + i, jnp.int32)
        logits, cache = step(params, tok, cache, pos)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out.append(tok)
    return np.concatenate([np.asarray(t) for t in out], axis=1)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default="smollm-360m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--out", default="/tmp/generations.rntj")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = smoke_config(args.arch) if args.smoke else get_arch(args.arch)
    bundle = build(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)

    shape = ((args.requests, args.prompt_len)
             if cfg.n_codebooks == 1
             else (args.requests, args.prompt_len, cfg.n_codebooks))
    prompts = rng.integers(0, cfg.vocab_size, shape).astype(np.int32)
    gen = generate(bundle, params, jnp.asarray(prompts), args.max_new)
    if gen.ndim == 3:
        gen = gen[..., 0]  # log first codebook stream

    writer = ParallelWriter(GEN_SCHEMA, args.out)
    ctx = writer.create_fill_context()
    sizes = np.full(args.requests, gen.shape[1], np.int64)
    ctx.fill_batch(ColumnBatch.from_arrays(GEN_SCHEMA, args.requests, {
        "request_id": np.arange(args.requests, dtype=np.int64),
        "prompt_len": np.full(args.requests, args.prompt_len, np.int32),
        "tokens": sizes,
        "tokens._0": gen.reshape(-1).astype(np.int32),
    }))
    ctx.close()
    writer.close()
    print(f"wrote {args.requests} generations x {gen.shape[1]} tokens -> {args.out}")


if __name__ == "__main__":
    main()
