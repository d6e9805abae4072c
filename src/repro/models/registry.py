"""Model registry: ArchConfig -> ModelBundle (init/loss/prefill/decode)."""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.archs import get_arch
from repro.configs.base import ArchConfig

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

    # -- shapes ----------------------------------------------------------------

    def param_shapes(self):
        return jax.eval_shape(lambda: self.init(jax.random.PRNGKey(0)))

    def _token_shape(self, batch: int, seq: int) -> Tuple[int, ...]:
        if self.cfg.n_codebooks > 1:
            return (batch, seq, self.cfg.n_codebooks)
        return (batch, seq)


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
