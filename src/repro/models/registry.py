"""Uniform Model interface over the architecture zoo.

`build_model(cfg)` returns a `Model` whose members close over the config:

    init(key) -> params            axes() -> logical-axes tree (same struct)
    loss(params, batch) -> scalar  (training objective)
    prefill(params, batch, cache) -> (logits, cache)
    decode(params, tokens, cache) -> (logits, cache)
    init_cache(batch, max_len) -> cache     cache_axes() -> axes tree
    serving_params(params) -> params (the tree the serving engine's step
        programs read; identity unless the family defines it)
    input_spec(shape_cell) handled by repro.launch.specs.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

import jax

from repro.configs.base import ArchConfig

from . import rwkv6, ssm_lm, transformer


@dataclass(frozen=True)
class Model:
    cfg: ArchConfig
    init: Callable
    axes: Callable
    loss: Callable
    prefill: Callable
    decode: Callable
    init_cache: Callable
    cache_axes: Callable
    serving_params: Callable = lambda p: p


def build_model(cfg: ArchConfig) -> Model:
    if cfg.family in ("dense", "moe", "audio", "vlm"):
        return Model(
            cfg=cfg,
            init=lambda key: transformer.init_params(cfg, key),
            axes=lambda: transformer.logical_axes(cfg),
            loss=lambda p, b: transformer.loss_fn(p, cfg, b),
            prefill=lambda p, b, c: transformer.prefill(p, cfg, b, c),
            decode=lambda p, t, c: transformer.decode_step(p, cfg, t, c),
            init_cache=lambda b, s: transformer.init_cache(cfg, b, s),
            cache_axes=lambda: transformer.cache_axes(cfg),
            serving_params=lambda p: transformer.serving_params(p, cfg),
        )
    if cfg.family == "ssm":
        return Model(
            cfg=cfg,
            init=lambda key: ssm_lm.rwkv_init(cfg, key),
            axes=lambda: ssm_lm.rwkv_axes(cfg),
            loss=lambda p, b: ssm_lm.rwkv_loss(p, cfg, b),
            prefill=lambda p, b, c: ssm_lm.rwkv_prefill(p, cfg, b, c),
            decode=lambda p, t, c: ssm_lm.rwkv_decode(p, cfg, t, c),
            init_cache=lambda b, s: rwkv6.state_init(cfg, b),
            cache_axes=lambda: rwkv6.state_axes(cfg),
        )
    if cfg.family == "hybrid":
        return Model(
            cfg=cfg,
            init=lambda key: ssm_lm.zamba_init(cfg, key),
            axes=lambda: ssm_lm.zamba_axes(cfg),
            loss=lambda p, b: ssm_lm.zamba_loss(p, cfg, b),
            prefill=lambda p, b, c: ssm_lm.zamba_prefill(p, cfg, b, c),
            decode=lambda p, t, c: ssm_lm.zamba_decode(p, cfg, t, c),
            init_cache=lambda b, s: ssm_lm.zamba_state_init(cfg, b, s),
            cache_axes=lambda: ssm_lm.zamba_state_axes(cfg),
        )
    raise ValueError(f"unknown family {cfg.family!r}")


def assert_axes_match(params, axes) -> None:
    """Every param leaf must have a logical-axes tuple of matching rank."""
    pstruct = jax.tree.structure(params)
    astruct = jax.tree.structure(axes, is_leaf=lambda x: isinstance(x, tuple))
    if pstruct != astruct:
        raise AssertionError(
            f"param/axes tree mismatch:\n{pstruct}\nvs\n{astruct}"
        )
    for p, a in zip(
        jax.tree.leaves(params),
        jax.tree.leaves(axes, is_leaf=lambda x: isinstance(x, tuple)),
    ):
        if len(a) != p.ndim:
            raise AssertionError(f"axes {a} rank != param shape {p.shape}")
