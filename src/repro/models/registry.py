"""Model registry: ArchConfig -> ModelBundle (init/loss/prefill/decode).

Also provides ``input_specs`` — ShapeDtypeStruct stand-ins for every model
input of a given (arch x shape-cell), the pattern the multi-pod dry-run
lowers against (no allocation).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.archs import get_arch
from repro.configs.base import ArchConfig, ShapeCell

from . import backbone as B


@dataclass
class ModelBundle:
    cfg: ArchConfig

    def init(self, rng) -> Dict:
        return B.init_params(rng, self.cfg)

    def loss(self, params, batch) -> Tuple[jax.Array, Dict]:
        return B.loss_fn(params, batch, self.cfg)

    def state_mask(self, params) -> Dict:
        return B.state_mask(params)

    def after_step(self, params, metrics) -> Tuple[Dict, Dict]:
        return B.after_step(params, metrics, self.cfg)

    def forward(self, params, tokens):
        return B.forward(params, tokens, self.cfg)

    def prefill(self, params, tokens, max_len: int, cache_dtype=None):
        return B.prefill(params, tokens, self.cfg, max_len, cache_dtype)

    def init_cache(self, batch: int, max_len: int, dtype=None):
        return B.init_cache(self.cfg, batch, max_len, dtype)

    def decode_step(self, params, tokens, cache, pos):
        return B.decode_step(params, tokens, cache, pos, self.cfg)

    # -- dry-run specs -------------------------------------------------------

    def param_shapes(self):
        return jax.eval_shape(lambda: self.init(jax.random.PRNGKey(0)))

    def _token_shape(self, batch: int, seq: int) -> Tuple[int, ...]:
        if self.cfg.n_codebooks > 1:
            return (batch, seq, self.cfg.n_codebooks)
        return (batch, seq)

    def input_specs(self, cell: ShapeCell) -> Dict[str, Any]:
        """ShapeDtypeStruct stand-ins for the cell's step function inputs.

        The modality frontends of [vlm]/[audio] archs are stubs: specs are
        precomputed token ids (chameleon VQ codes / EnCodec codebook codes).
        """
        i32 = jnp.int32
        if cell.kind == "train":
            return {
                "tokens": jax.ShapeDtypeStruct(
                    self._token_shape(cell.global_batch, cell.seq_len), i32),
                "labels": jax.ShapeDtypeStruct(
                    self._token_shape(cell.global_batch, cell.seq_len), i32),
            }
        if cell.kind == "prefill":
            return {
                "tokens": jax.ShapeDtypeStruct(
                    self._token_shape(cell.global_batch, cell.seq_len), i32),
            }
        # decode: one new token against a cache of cell.seq_len
        cache_shapes = jax.eval_shape(
            lambda: self.init_cache(cell.global_batch, cell.seq_len)
        )
        return {
            "tokens": jax.ShapeDtypeStruct(
                self._token_shape(cell.global_batch, 1), i32),
            "cache": cache_shapes,
            "pos": jax.ShapeDtypeStruct((cell.global_batch,), i32),
        }

    def runnable(self, cell: ShapeCell) -> Tuple[bool, str]:
        """Is this (arch x cell) runnable? long_500k needs sub-quadratic."""
        if cell.name == "long_500k" and not self.cfg.sub_quadratic:
            return False, "SKIP(full-attn): 500k dense decode cache unbounded"
        return True, ""


def build(cfg_or_name) -> ModelBundle:
    cfg = cfg_or_name if isinstance(cfg_or_name, ArchConfig) else get_arch(cfg_or_name)
    return ModelBundle(cfg)


def make_batch(bundle: ModelBundle, rng: np.random.Generator, batch: int,
               seq: int) -> Dict[str, jax.Array]:
    """Random token batch for smoke tests / examples."""
    shape = bundle._token_shape(batch, seq)
    toks = rng.integers(0, bundle.cfg.vocab_size, shape).astype(np.int32)
    labels = np.roll(toks, -1, axis=1)
    return {"tokens": jnp.asarray(toks), "labels": jnp.asarray(labels)}
