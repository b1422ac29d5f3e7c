"""Unified model API over all assigned architectures.

``build_model(cfg)`` returns a :class:`Model` exposing:
  * ``init(key)`` → params
  * ``loss_fn(params, batch, ...)`` → (loss, metrics)
  * ``train_step(state, batch)`` → (state, metrics)   (AdamW + clipping)
  * ``prefill(params, batch)`` → (logits, cache)
  * ``decode_step(params, batch)`` → (logits, cache)
  * ``input_specs(cell)`` / ``state_specs()`` — ShapeDtypeStruct stand-ins for
    the dry-run (no allocation).

Batch layouts (all int32 tokens):
  train:   {"tokens": (B, S+1)} (+ "patches"/"frames" for vlm/encdec stubs)
  prefill: {"tokens": (B, S)} (+ stub inputs)
  decode:  {"tokens": (B, 1), "pos": () int32, "cache": pytree}
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeCell
from repro.models import layers as L
from repro.models import mamba2, rwkv6, transformer, whisper, zamba2
from repro.optim import make_optimizer
from repro.optim.adamw import clip_by_global_norm

AUX_COEF = 0.01


def _family_forward(cfg):
    if cfg.family in ("dense", "moe", "vlm"):
        return transformer.forward
    if cfg.family == "encdec":
        return whisper.forward
    if cfg.family == "ssm":
        return rwkv6.forward
    if cfg.family == "hybrid":
        return zamba2.forward
    raise ValueError(cfg.family)


@dataclasses.dataclass
class Model:
    cfg: ArchConfig

    # ------------------------------------------------------------------ init
    def init(self, key):
        cfg = self.cfg
        if cfg.family in ("dense", "moe", "vlm"):
            return transformer.init_transformer(cfg, key)
        if cfg.family == "encdec":
            return whisper.init_whisper(cfg, key)
        if cfg.family == "ssm":
            return rwkv6.init_rwkv6(cfg, key)
        if cfg.family == "hybrid":
            return zamba2.init_zamba2(cfg, key)
        raise ValueError(cfg.family)

    def param_specs(self):
        return jax.eval_shape(lambda: self.init(jax.random.PRNGKey(0)))

    # ------------------------------------------------------------------ loss
    def loss_fn(self, params, batch, *, n_groups=1, use_pallas=False):
        cfg = self.cfg
        tokens = batch["tokens"]
        inputs, labels = tokens[:, :-1], tokens[:, 1:]
        fwd = _family_forward(cfg)
        kwargs = dict(n_groups=n_groups, use_pallas=use_pallas)
        if cfg.family == "vlm":
            kwargs["patch_embeds"] = batch["patches"]
        if cfg.family == "encdec":
            kwargs["frames"] = batch["frames"]
        hidden, aux = fwd(cfg, params, inputs, return_hidden=True, **kwargs)
        loss = L.chunked_cross_entropy(params["embed"], hidden, labels, cfg)
        total = loss + AUX_COEF * aux
        return total, {"loss": loss, "aux_loss": aux}

    # ------------------------------------------------------------ train step
    def make_train_step(self, *, n_groups=1, use_pallas=False, donate=True):
        cfg = self.cfg
        opt = make_optimizer(cfg)

        def train_step(state, batch):
            params, opt_state = state["params"], state["opt"]

            def lf(p):
                return self.loss_fn(p, batch, n_groups=n_groups,
                                    use_pallas=use_pallas)

            (tot, metrics), grads = jax.value_and_grad(lf, has_aux=True)(params)
            with jax.named_scope("optimizer"):
                grads, gnorm = clip_by_global_norm(grads, cfg.grad_clip)
                updates, new_opt = opt.update(grads, opt_state, params)
                new_params = jax.tree.map(lambda p, u: p - u.astype(p.dtype),
                                          params, updates)
            metrics = dict(metrics, grad_norm=gnorm, total_loss=tot)
            return {"params": new_params, "opt": new_opt}, metrics

        return train_step

    def init_train_state(self, key):
        params = self.init(key)
        opt = make_optimizer(self.cfg)
        return {"params": params, "opt": opt.init(params)}

    def train_state_specs(self):
        return jax.eval_shape(lambda: self.init_train_state(jax.random.PRNGKey(0)))

    # ------------------------------------------------------------ serving
    def prefill(self, params, batch, *, use_pallas=False):
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S = tokens.shape
        fwd = _family_forward(cfg)
        if cfg.family in ("dense", "moe", "vlm"):
            prefix = cfg.n_patches if cfg.family == "vlm" else 0
            cache = transformer.make_cache(cfg, B, S, prefix=prefix)
            kwargs = {}
            if cfg.family == "vlm":
                kwargs["patch_embeds"] = batch["patches"]
            logits, cache, _ = fwd(cfg, params, tokens, cache=cache,
                                   cache_pos=jnp.zeros((), jnp.int32),
                                   use_pallas=use_pallas, last_only=True,
                                   **kwargs)
            return logits, cache
        if cfg.family == "encdec":
            cache = whisper.make_cache(cfg, B, S)
            logits, cache, _ = whisper.forward(cfg, params, tokens,
                                               frames=batch["frames"], cache=cache,
                                               cache_pos=jnp.zeros((), jnp.int32),
                                               use_pallas=use_pallas,
                                               last_only=True)
            return logits, cache
        if cfg.family == "ssm":
            state = rwkv6.make_state(cfg, B)
            logits, state, _ = rwkv6.forward(cfg, params, tokens, state=state,
                                             use_pallas=use_pallas,
                                             last_only=True)
            return logits, state
        if cfg.family == "hybrid":
            state = zamba2.make_state(cfg, B, S)
            logits, state, _ = zamba2.forward(cfg, params, tokens, state=state,
                                              use_pallas=use_pallas,
                                              last_only=True)
            return logits, state
        raise ValueError(cfg.family)

    def decode_step(self, params, batch, *, use_pallas=False):
        """batch: {"tokens": (B,1), "pos": (), "cache": pytree}."""
        cfg = self.cfg
        tokens, pos, cache = batch["tokens"], batch["pos"], batch["cache"]
        if cfg.family in ("dense", "moe", "vlm"):
            logits, cache, _ = transformer.forward(
                cfg, params, tokens, cache=cache, cache_pos=pos,
                use_pallas=use_pallas)
            return logits, cache
        if cfg.family == "encdec":
            logits, cache, _ = whisper.forward(cfg, params, tokens, cache=cache,
                                               cache_pos=pos, use_pallas=use_pallas)
            return logits, cache
        if cfg.family == "ssm":
            logits, state, _ = rwkv6.forward(cfg, params, tokens, state=cache,
                                             use_pallas=use_pallas)
            return logits, state
        if cfg.family == "hybrid":
            cache = dict(cache, pos=pos)
            logits, state, _ = zamba2.forward(cfg, params, tokens, state=cache,
                                              use_pallas=use_pallas)
            return logits, state
        raise ValueError(cfg.family)

    # ------------------------------------------------------------ specs
    def input_specs(self, cell: ShapeCell) -> Dict[str, Any]:
        """ShapeDtypeStruct stand-ins for every model input of this cell."""
        cfg = self.cfg
        B, S = cell.global_batch, cell.seq_len
        i32 = jnp.int32
        bf16 = jnp.bfloat16
        if cell.kind == "train":
            specs = {"tokens": jax.ShapeDtypeStruct((B, S + 1), i32)}
            if cfg.family == "vlm":
                specs["patches"] = jax.ShapeDtypeStruct((B, cfg.n_patches, cfg.d_model), bf16)
            if cfg.family == "encdec":
                specs["frames"] = jax.ShapeDtypeStruct((B, cfg.enc_len, cfg.d_model), bf16)
            return specs
        if cell.kind == "prefill":
            specs = {"tokens": jax.ShapeDtypeStruct((B, S), i32)}
            if cfg.family == "vlm":
                specs["patches"] = jax.ShapeDtypeStruct((B, cfg.n_patches, cfg.d_model), bf16)
            if cfg.family == "encdec":
                specs["frames"] = jax.ShapeDtypeStruct((B, cfg.enc_len, cfg.d_model), bf16)
            return specs
        # decode
        return {
            "tokens": jax.ShapeDtypeStruct((B, 1), i32),
            "pos": jax.ShapeDtypeStruct((), i32),
            "cache": self.cache_specs(B, S),
        }

    def cache_specs(self, batch, max_len):
        cfg = self.cfg
        if cfg.family in ("dense", "moe"):
            return transformer.cache_specs(cfg, batch, max_len)
        if cfg.family == "vlm":
            return transformer.cache_specs(cfg, batch, max_len, prefix=cfg.n_patches)
        if cfg.family == "encdec":
            return whisper.cache_specs(cfg, batch, max_len)
        if cfg.family == "ssm":
            return rwkv6.state_specs(cfg, batch)
        if cfg.family == "hybrid":
            return zamba2.state_specs(cfg, batch, max_len)
        raise ValueError(cfg.family)

    def make_batch(self, cell: ShapeCell, key=None):
        """Concrete random batch matching input_specs (smoke tests/examples)."""
        key = key if key is not None else jax.random.PRNGKey(0)
        specs = self.input_specs(cell)

        def mk(path, s):
            if s.dtype == jnp.int32 and s.shape:
                return jax.random.randint(key, s.shape, 0, self.cfg.vocab, jnp.int32)
            if s.dtype == jnp.int32:
                return jnp.asarray(max(0, cell.seq_len - 1), jnp.int32)
            return jax.random.normal(key, s.shape, jnp.float32).astype(s.dtype) * 0.02

        return jax.tree_util.tree_map_with_path(mk, specs)


def build_model(cfg: ArchConfig) -> Model:
    return Model(cfg)
