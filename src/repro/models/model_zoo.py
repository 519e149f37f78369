"""Public model API: ArchConfig -> init / loss / prefill / decode callables."""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.models import transformer
from repro.models.layers import RunConfig


class Model:
    """Thin functional bundle for one architecture."""

    def __init__(self, cfg, rc: Optional[RunConfig] = None):
        self.cfg = cfg
        self.rc = rc or RunConfig()

    # -- parameters -----------------------------------------------------
    def init(self, key) -> Dict[str, Any]:
        return transformer.init_params(self.cfg, key, self.rc)

    def init_eval_shape(self):
        """Parameter ShapeDtypeStructs without allocating (dry-run path)."""
        return jax.eval_shape(lambda: transformer.init_params(
            self.cfg, jax.random.PRNGKey(0), self.rc))

    # -- training -------------------------------------------------------
    def loss(self, params, batch) -> jax.Array:
        return self.loss_and_stats(params, batch)[0]

    def loss_and_stats(self, params, batch):
        """(loss, MoE routing totals: a dict, empty without MoE layers)."""
        logits, aux, _, stats = self._forward(params, batch)
        return transformer.lm_loss(logits, batch["labels"], self.cfg, aux,
                                   aux_weight=self.cfg.aux_loss_alpha), stats

    def apply(self, params, batch, return_cache: bool = False,
              last_only: bool = False):
        """(logits, aux loss, cache)."""
        return self._forward(params, batch, return_cache=return_cache,
                             last_only=last_only)[:3]

    def _forward(self, params, batch, **kw):
        cfg = self.cfg
        if cfg.frontend == "audio":
            return transformer.forward(params, cfg, self.rc,
                                       embeds=batch["embeds"], **kw)
        if cfg.frontend == "vision":
            return transformer.forward(params, cfg, self.rc,
                                       tokens=batch["tokens"],
                                       img_embeds=batch["img_embeds"], **kw)
        return transformer.forward(params, cfg, self.rc,
                                   tokens=batch["tokens"], **kw)

    # -- serving ----------------------------------------------------------
    def prefill(self, params, batch):
        logits, _, cache = self.apply(params, batch, return_cache=True,
                                      last_only=True)
        return logits, cache

    def decode(self, params, cache, batch):
        """(logits, cache)."""
        return self.decode_and_stats(params, cache, batch)[:2]

    def decode_and_stats(self, params, cache, batch):
        """(logits, cache, MoE routing totals: empty without MoE layers)."""
        cfg = self.cfg
        if cfg.frontend == "audio":
            return transformer.decode_step(params, cfg, self.rc, cache,
                                           None, embeds=batch["embeds"])
        return transformer.decode_step(params, cfg, self.rc, cache,
                                       batch["tokens"])

    def init_cache(self, batch: int, max_len: int):
        return transformer.init_cache(self.cfg, self.rc, batch, max_len)

    def init_cache_eval_shape(self, batch: int, max_len: int):
        return jax.eval_shape(lambda: self.init_cache(batch, max_len))


def build(cfg, rc: Optional[RunConfig] = None) -> Model:
    return Model(cfg, rc)
