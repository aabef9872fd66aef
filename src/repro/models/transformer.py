"""Generic transformer LM (dense / MoE / encoder-only / VLM backbone).

Covers: gemma-2b, qwen3-14b, nemotron-4-340b, llama3.2-1b, hubert-xlarge
(encoder), llava-next-mistral-7b (VLM stub frontend), mixtral-8x22b,
phi3.5-moe, and Mellum2-12B-A2.5B (the benchmark's adapter: spiking
experts, 3:1 sliding/full attention with YaRN on the full layers).  Layers
are scanned (compile-time O(1) in depth) with optional remat; a model with
per-layer attention kinds (``cfg.layer_types``) scans each layer's kind
beside its weights.  The residual stream between layers carries SP
sharding constraints (applied by the train/serve steps via shard hooks).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig

from .layers import (
    _ct,
    _dt,
    attn_apply,
    attn_axes,
    attn_init,
    dense_init,
    mlp_apply,
    mlp_axes,
    mlp_init,
    moe_apply,
    moe_axes,
    moe_init,
    layer_kinds,
    rmsnorm,
    spiking_moe_apply,
)

# A hook the distributed layer installs to constrain intermediate shardings
# (identity by default so models are runnable without a mesh).
_shard_hook = lambda x, name: x


def set_shard_hook(fn):
    global _shard_hook
    _shard_hook = fn


def block_init(key, cfg: ArchConfig) -> dict:
    k1, k2 = jax.random.split(key)
    p = {
        "ln1": jnp.zeros((cfg.d_model,), _dt(cfg)),
        "attn": attn_init(k1, cfg),
        "ln2": jnp.zeros((cfg.d_model,), _dt(cfg)),
    }
    if cfg.n_experts:
        p["moe"] = moe_init(k2, cfg)
    else:
        p["mlp"] = mlp_init(k2, cfg)
    return p


def block_axes(cfg: ArchConfig) -> dict:
    ax = {"ln1": (None,), "attn": attn_axes(cfg), "ln2": (None,)}
    if cfg.n_experts:
        ax["moe"] = moe_axes(cfg)
    else:
        ax["mlp"] = mlp_axes(cfg)
    return ax


def block_apply(p, x, cfg: ArchConfig, positions=None, cache=None,
                sliding=None):
    """Pre-norm transformer block. Returns (x, new_cache, aux_loss).

    ``sliding``: the layer's kind in a `layer_types` model (see
    `layers.attn_apply`).  Its two halves are the named scopes
    ``attention`` and ``ffn`` of the compiled program (norm and residual
    add included)."""
    with jax.named_scope("attention"):
        h, new_cache = attn_apply(
            p["attn"], rmsnorm(x, p["ln1"], cfg.norm_eps), cfg,
            positions=positions, cache=cache, sliding=sliding,
        )
        x = x + h
        x = _shard_hook(x, "residual")
    with jax.named_scope("ffn"):
        h2 = rmsnorm(x, p["ln2"], cfg.norm_eps)
        if cfg.n_experts and cfg.spiking_ffn:
            h2, aux = spiking_moe_apply(p["moe"], h2, cfg)
        elif cfg.n_experts:
            h2, aux = moe_apply(p["moe"], h2, cfg)
        else:
            h2, aux = mlp_apply(p["mlp"], h2, cfg), 0.0
        x = x + h2
        x = _shard_hook(x, "residual")
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# full model
# ---------------------------------------------------------------------------

def init_params(cfg: ArchConfig, key) -> dict:
    ks = jax.random.split(key, 4)
    p: dict = {}
    if cfg.embed_inputs:
        p["embed"] = dense_init(ks[0], (cfg.vocab, cfg.d_model), _dt(cfg), fan_in=cfg.d_model)
    layer_keys = jax.random.split(ks[1], cfg.n_layers)
    p["layers"] = jax.vmap(lambda k: block_init(k, cfg))(layer_keys)
    p["final_norm"] = jnp.zeros((cfg.d_model,), _dt(cfg))
    if cfg.encoder_only:
        p["head"] = dense_init(ks[2], (cfg.d_model, cfg.vocab), _dt(cfg))
    elif not cfg.tie_embeddings:
        p["lm_head"] = dense_init(ks[2], (cfg.d_model, cfg.vocab), _dt(cfg))
    if cfg.n_img_tokens:
        # multimodal projector (frontend itself is stubbed: patch embeddings
        # arrive precomputed at vision-encoder width == d_model here)
        p["mm_proj"] = dense_init(ks[3], (cfg.d_model, cfg.d_model), _dt(cfg))
    if not cfg.embed_inputs:
        # audio stub: frame embeddings arrive at d_model; learned input norm
        p["in_norm"] = jnp.zeros((cfg.d_model,), _dt(cfg))
    return p


def serving_params(p, cfg: ArchConfig) -> dict:
    """The param tree as the serving step programs read it.

    Every leaf the model reads only through ``.astype(compute_dtype)`` is
    stored in the compute dtype once, so a step program reads it directly
    instead of converting the whole stacked tensor on every call: the
    attention projections, the untied ``lm_head``, ``mm_proj``, and the
    dense or expert MLP weights.  The values reaching each dot are the ones
    the in-program cast gave (both round to nearest even).  Left as they
    are: ``embed`` (the packed spike encode and a tied head read it in
    f32), the norm scales, the MoE ``router``, and the spiking FFN's
    ``wu``/``wd``, dense or expert (`layers.attach_spiking_ffn_plans`
    builds its own compute-dtype payload from them).  A leaf already in the
    compute dtype is returned as it is, so the transform is idempotent.
    Training keeps its f32 masters and never calls this."""
    ct = _ct(cfg)

    def cast(a):
        return a if a.dtype == ct else a.astype(ct)

    layers = dict(p["layers"])
    layers["attn"] = {k: cast(v) if k in ("wq", "wk", "wv", "wo") else v
                      for k, v in layers["attn"].items()}
    if "moe" in layers and not cfg.spiking_ffn:
        layers["moe"] = {k: v if k == "router" else cast(v)
                         for k, v in layers["moe"].items()}
    elif not cfg.spiking_ffn:
        layers["mlp"] = {k: cast(v) for k, v in layers["mlp"].items()}
    out = dict(p, layers=layers)
    for k in ("lm_head", "mm_proj"):
        if k in out:
            out[k] = cast(out[k])
    return out


def logical_axes(cfg: ArchConfig) -> dict:
    ax: dict = {}
    if cfg.embed_inputs:
        ax["embed"] = ("vocab", "d_model")
    stack = lambda t: jax.tree.map(lambda a: ("layers",) + a, block_axes(cfg),
                                   is_leaf=lambda x: isinstance(x, tuple))
    ax["layers"] = stack(None)
    ax["final_norm"] = (None,)
    if cfg.encoder_only:
        ax["head"] = ("d_model", "vocab")
    elif not cfg.tie_embeddings:
        ax["lm_head"] = ("d_model", "vocab")
    if cfg.n_img_tokens:
        ax["mm_proj"] = ("d_model", "d_model")
    if not cfg.embed_inputs:
        ax["in_norm"] = (None,)
    return ax


def _stack_forward(p_layers, x, cfg: ArchConfig, positions):
    """Scan the layer stack (training/prefill, no cache)."""
    kinds = layer_kinds(cfg)

    def body(carry, inp):
        x, aux = carry
        lp, sliding = inp
        x, _, a = block_apply(lp, x, cfg, positions=positions,
                              sliding=sliding)
        return (x, aux + a), None

    body_fn = jax.remat(body) if cfg.remat else body
    if cfg.scan_layers:
        (x, aux), _ = jax.lax.scan(
            body_fn, (x, 0.0), (p_layers, kinds), unroll=cfg.scan_unroll
        )
    else:
        carry = (x, 0.0)
        for i in range(cfg.n_layers):
            inp = jax.tree.map(lambda a: a[i], (p_layers, kinds))
            carry, _ = body_fn(carry, inp)
        x, aux = carry
    return x, aux


@jax.named_scope("embed")
def embed_tokens(p, cfg: ArchConfig, tokens):
    e = p["embed"][tokens].astype(_ct(cfg))
    if cfg.name.startswith("gemma"):
        e = e * jnp.asarray(cfg.d_model ** 0.5, e.dtype)
    return e


def forward(p, cfg: ArchConfig, batch: dict):
    """Training/eval forward -> (logits_input_embedding x, aux).

    batch: {tokens (B,S)} or {frames (B,S,D)} (audio stub) or
    {tokens, img_embed (B,n_img,D)} (vlm stub).
    """
    if cfg.embed_inputs:
        x = embed_tokens(p, cfg, batch["tokens"])
        if cfg.n_img_tokens:
            img = batch["img_embed"].astype(_ct(cfg)) @ p["mm_proj"].astype(_ct(cfg))
            x = jnp.concatenate([img, x[:, : x.shape[1] - img.shape[1]]], axis=1)
    else:
        x = rmsnorm(batch["frames"].astype(_ct(cfg)), p["in_norm"], cfg.norm_eps)
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    x = _shard_hook(x, "residual")
    x, aux = _stack_forward(p["layers"], x, cfg, positions)
    x = rmsnorm(x, p["final_norm"], cfg.norm_eps)
    return x, aux


@jax.named_scope("head")
def head(p, cfg: ArchConfig, x, last: bool = False):
    """Final norm and unembedding (of the last position alone with
    ``last``): the named scope ``head``."""
    x = rmsnorm(x, p["final_norm"], cfg.norm_eps)
    return unembed(p, cfg, x[:, -1:] if last else x)


def unembed(p, cfg: ArchConfig, x):
    if cfg.encoder_only:
        w = p["head"]
    elif cfg.tie_embeddings:
        w = p["embed"].T
    else:
        w = p["lm_head"]
    return jnp.einsum(
        "bsd,dv->bsv", x.astype(_ct(cfg)), w.astype(_ct(cfg)),
        preferred_element_type=jnp.float32,
    )


def ce_loss(p, cfg: ArchConfig, x, labels):
    """Token-level CE from final hidden states, with chunked vocab softmax
    (memory: cfg.loss_chunk tokens of logits live at once)."""
    B, S = labels.shape
    xt = x.reshape(B * S, -1)
    lt = labels.reshape(B * S)
    mask = (lt >= 0).astype(jnp.float32)
    lt = jnp.maximum(lt, 0)

    def ce(chunk):
        xc, lc = chunk
        logits = unembed(p, cfg, xc[None])[0]  # (c, V) f32
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        ll = jnp.sum(logits * jax.nn.one_hot(lc, logits.shape[-1], dtype=logits.dtype), axis=-1)
        return lse - ll

    c = cfg.loss_chunk
    if c and (B * S) % c == 0 and (B * S) > c:
        n = (B * S) // c
        losses = jax.lax.map(
            jax.remat(ce), (xt.reshape(n, c, -1), lt.reshape(n, c))
        ).reshape(B * S)
    else:
        losses = ce((xt, lt))
    return jnp.sum(losses * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def loss_fn(p, cfg: ArchConfig, batch: dict):
    x, aux = forward(p, cfg, batch)
    loss = ce_loss(p, cfg, x, batch["labels"])
    if cfg.n_experts:
        loss = loss + 0.01 * aux / cfg.n_layers
    return loss


# ---------------------------------------------------------------------------
# serving: prefill + decode with KV cache
# ---------------------------------------------------------------------------

def init_cache(cfg: ArchConfig, batch: int, max_len: int, dtype=jnp.bfloat16,
               full: bool = False):
    S = min(max_len, cfg.window) if (cfg.attn == "swa" and not full) else max_len
    shape = (cfg.n_layers, batch, S, cfg.n_kv, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "kv_pos": -jnp.ones((S,), jnp.int32),  # -1 = empty ring slot
        "pos": jnp.zeros((), jnp.int32),
    }


def cache_axes(cfg: ArchConfig) -> dict:
    return {
        "k": ("layers", "batch", "cache_seq", "kv_heads", None),
        "v": ("layers", "batch", "cache_seq", "kv_heads", None),
        "kv_pos": (None,),
        "pos": (),
    }


def _stack_forward_cached(p_layers, x, cfg: ArchConfig, positions, cache):
    """Scan layers threading per-layer KV cache (leading L dim)."""
    def body(carry, inp):
        x = carry
        lp, ck, cv, sliding = inp
        lc = {"k": ck, "v": cv, "kv_pos": cache["kv_pos"], "pos": cache["pos"]}
        x, nc, _ = block_apply(lp, x, cfg, positions=positions, cache=lc,
                               sliding=sliding)
        return x, (nc["k"], nc["v"])

    x, (nk, nv) = jax.lax.scan(
        body, x, (p_layers, cache["k"], cache["v"], layer_kinds(cfg)))
    S = x.shape[1]
    s_cache = cache["k"].shape[2]
    kv_pos = jax.lax.dynamic_update_slice(
        cache["kv_pos"],
        cache["pos"] + jnp.arange(S, dtype=jnp.int32),
        (cache["pos"] % s_cache,),
    )
    new_cache = {"k": nk, "v": nv, "kv_pos": kv_pos, "pos": cache["pos"] + S}
    return x, new_cache


def prefill(p, cfg: ArchConfig, batch: dict, cache):
    """Process the full prompt, fill the cache, return last-token logits.

    Encoder-only archs (hubert): prefill == the encoder forward over the
    whole input (there is no decode); returns frame logits for the last
    position and the untouched (empty) cache."""
    if cfg.encoder_only:
        x, _ = forward(p, cfg, batch)
        return unembed(p, cfg, x[:, -1:]), cache
    if cfg.embed_inputs:
        x = embed_tokens(p, cfg, batch["tokens"])
        if cfg.n_img_tokens:
            img = batch["img_embed"].astype(_ct(cfg)) @ p["mm_proj"].astype(_ct(cfg))
            x = jnp.concatenate([img, x[:, : x.shape[1] - img.shape[1]]], axis=1)
    else:
        x = rmsnorm(batch["frames"].astype(_ct(cfg)), p["in_norm"], cfg.norm_eps)
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    x = _shard_hook(x, "residual")
    if cfg.attn == "swa" and S > cache["k"].shape[2]:
        # SWA prompt longer than the window-sized ring cache: run through a
        # temporary full-length cache (seq-sharded; see sharding rules), then
        # keep only the last `window` entries.  When window | S the ring slots
        # align with a plain tail slice.
        w = cache["k"].shape[2]
        assert S % w == 0, "SWA prefill requires window | seq_len"
        tmp = init_cache(cfg, B, S, dtype=cache["k"].dtype, full=True)
        x, full = _stack_forward_cached(p["layers"], x, cfg, positions, tmp)
        new_cache = {
            "k": full["k"][:, :, S - w:],
            "v": full["v"][:, :, S - w:],
            "kv_pos": full["kv_pos"][S - w:],
            "pos": full["pos"],
        }
    else:
        x, new_cache = _stack_forward_cached(p["layers"], x, cfg, positions, cache)
    return head(p, cfg, x, last=True), new_cache


def decode_step(p, cfg: ArchConfig, tokens, cache):
    """One decode step: tokens (B, S) -> (logits (B, S, V), new cache).

    S is usually 1; S > 1 is the speculative-verify window (all k+1
    positions of one round in one dispatch) and the event-stream frame
    chunk.  Positions are absolute (``cache["pos"] + arange(S)``), so the
    causal mask inside the window falls out of the standard
    ``kv_pos <= query_pos`` comparison — per-position logits are bitwise
    identical to S chained single-token steps.
    """
    x = embed_tokens(p, cfg, tokens) if cfg.embed_inputs else tokens
    B, S = x.shape[0], x.shape[1]
    positions = jnp.broadcast_to(
        cache["pos"][None, None] + jnp.arange(S)[None, :], (B, S)
    )
    x, new_cache = _stack_forward_cached(p["layers"], x, cfg, positions, cache)
    return head(p, cfg, x), new_cache
