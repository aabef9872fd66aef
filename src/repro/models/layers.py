"""Primitive layers shared by the architecture zoo.

Pure-functional: params are plain dict pytrees; a parallel `*_axes` function
returns the logical sharding axes for every leaf (same tree structure —
enforced by tests).  Compute in cfg.compute_dtype (bf16), reductions and
softmax in f32.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig

# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(key, shape, dtype, fan_in=None):
    fan_in = fan_in if fan_in is not None else shape[0]
    return (jax.random.normal(key, shape) / math.sqrt(fan_in)).astype(dtype)


def _dt(cfg: ArchConfig):
    return jnp.dtype(cfg.param_dtype)


def _ct(cfg: ArchConfig):
    return jnp.dtype(cfg.compute_dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rmsnorm(x, scale, eps=1e-5):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_apply(x, positions, theta, freqs=None, scale=None):
    """x: (B, S, H, dh); positions: (B, S) int32.  ``freqs``/``scale``:
    a layer's own (half,) inverse frequencies and cos/sin factor
    (`rope_table`); default: ``theta``'s plain table, factor 1."""
    dh = x.shape[-1]
    half = dh // 2
    if freqs is None:
        freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (B, S, half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    if scale is not None:
        cos, sin = cos * scale, sin * scale
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def yarn_inv_freq(dh: int, theta: float, factor: float, original_max_pos: int,
                  beta_fast: float, beta_slow: float):
    """YaRN's (dh // 2,) inverse frequencies and attention factor, as
    Hugging Face ``_compute_yarn_parameters`` defines them (``truncate``
    true): dimensions rotating more than ``beta_fast`` times over the
    original context keep their frequency, fewer than ``beta_slow`` are
    divided by ``factor``, with a linear ramp between."""
    import numpy as np

    half = dh // 2
    pos_freqs = theta ** (np.arange(0, dh, 2, dtype=np.float32) / dh)
    extra, inter = 1.0 / pos_freqs, 1.0 / (factor * pos_freqs)

    def dim(rot):
        return (dh * math.log(original_max_pos / (rot * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim(beta_fast)), 0)
    high = min(math.ceil(dim(beta_slow)), dh - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float32) - low) / (high - low),
                   0.0, 1.0)
    extra_share = 1.0 - ramp
    inv = inter * (1.0 - extra_share) + extra * extra_share
    return inv.astype(np.float32), 0.1 * math.log(factor) + 1.0


def rope_table(cfg: ArchConfig, sliding):
    """(inverse frequencies, cos/sin factor) of a `layer_types` layer:
    ``sliding`` (a traced bool) picks the plain table; full-attention
    layers take YaRN's when ``cfg.yarn_factor`` is set."""
    half = cfg.head_dim // 2
    plain = cfg.rope_theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    if not cfg.yarn_factor:
        return plain, None
    inv, default_af = yarn_inv_freq(
        cfg.head_dim, cfg.rope_theta, cfg.yarn_factor,
        cfg.yarn_original_max_pos, cfg.yarn_beta_fast, cfg.yarn_beta_slow)
    af = cfg.yarn_attention_factor or default_af
    return (jnp.where(sliding, plain, jnp.asarray(inv)),
            jnp.where(sliding, jnp.float32(1.0), jnp.float32(af)))


def layer_kinds(cfg: ArchConfig):
    """(n_layers,) bool, True for a sliding-window layer, or None when the
    model has no per-layer attention kinds."""
    if not cfg.layer_types:
        return None
    kinds = cfg.layer_types[: cfg.n_layers]
    if len(kinds) != cfg.n_layers or not set(kinds) <= {
            "sliding_attention", "full_attention"}:
        raise ValueError(f"{cfg.name}: layer_types {cfg.layer_types!r} do not "
                         f"give {cfg.n_layers} sliding/full layers")
    return jnp.asarray([k == "sliding_attention" for k in kinds])


# ---------------------------------------------------------------------------
# attention (GQA/MQA, causal/bidir/SWA, chunked-query exact softmax)
# ---------------------------------------------------------------------------

# Sharding-constraint hook for (B, S, H, dh) q/k/v tensors — installed by the
# distributed layer (sharding.make_qkv_hook); identity off-mesh.
_qkv_hook = lambda t: t


def set_qkv_hook(fn):
    global _qkv_hook
    _qkv_hook = fn

def attn_init(key, cfg: ArchConfig) -> dict:
    D, H, KV, dh = cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.head_dim
    ks = jax.random.split(key, 5)
    p = {
        "wq": dense_init(ks[0], (D, H * dh), _dt(cfg)),
        "wk": dense_init(ks[1], (D, KV * dh), _dt(cfg)),
        "wv": dense_init(ks[2], (D, KV * dh), _dt(cfg)),
        "wo": dense_init(ks[3], (H * dh, D), _dt(cfg)),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((dh,), _dt(cfg))
        p["k_norm"] = jnp.zeros((dh,), _dt(cfg))
    return p


def attn_axes(cfg: ArchConfig) -> dict:
    ax = {
        "wq": ("d_model", "heads_flat"),
        "wk": ("d_model", "kv_flat"),
        "wv": ("d_model", "kv_flat"),
        "wo": ("heads_flat", "d_model"),
    }
    if cfg.qk_norm:
        ax["q_norm"] = (None,)
        ax["k_norm"] = (None,)
    return ax


def _attn_mask(iq, jk, mode: str, window: int, kv_len=None, sliding=None):
    """iq: (cq,) absolute query positions; jk: (Skv,) absolute kv positions
    (may be a ring buffer's stored positions; -1 = empty slot).
    ``sliding``: a traced bool that adds the window to a causal mask (a
    `layer_types` layer)."""
    if mode == "bidir":
        m = jnp.ones((iq.shape[0], jk.shape[0]), bool)
    else:
        m = jk[None, :] <= iq[:, None]
        if mode == "swa":
            m &= jk[None, :] > (iq[:, None] - window)
        if sliding is not None:
            m &= jnp.logical_or(jnp.logical_not(sliding),
                                jk[None, :] > (iq[:, None] - window))
    m &= jk[None, :] >= 0
    if kv_len is not None:
        m &= jk[None, :] < kv_len
    return m


def multihead_attention(
    q, k, v, cfg: ArchConfig, *, q_offset=0, kv_len=None, mode=None,
    kv_positions=None, sliding=None,
):
    """q: (B, Sq, H, dh); k, v: (B, Skv, KV, dh) -> (B, Sq, H, dh).

    Exact softmax, chunked over queries (cfg.attn_chunk) so the (cq, Skv)
    score tile bounds live memory — the XLA-level analogue of flash attention
    for the dry-run memory budget.
    """
    mode = mode or cfg.attn
    B, Sq, H, dh = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, dh)
    scale = dh ** -0.5
    jk = jnp.arange(Skv) if kv_positions is None else kv_positions

    def chunk_attn(q_c, iq):
        # q_c: (B, cq, KV, G, dh)
        s = jnp.einsum(
            "bqkgd,bskd->bkgqs", q_c, k, preferred_element_type=jnp.float32
        ) * scale
        m = _attn_mask(iq, jk, mode, cfg.window, kv_len, sliding)
        s = jnp.where(m[None, None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum(
            "bkgqs,bskd->bqkgd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32,
        )
        return o.astype(q.dtype)

    cq = cfg.attn_chunk
    if cq and Sq > cq and Sq % cq == 0:
        qc = qg.reshape(B, Sq // cq, cq, KV, G, dh).transpose(1, 0, 2, 3, 4, 5)
        iqs = (q_offset + jnp.arange(Sq)).reshape(Sq // cq, cq)
        # remat: without it, differentiating lax.map saves every chunk's
        # (B, H, cq, Skv) probabilities — 19 GiB/layer on nemotron train_4k
        # (EXPERIMENTS.md §Perf iteration 3)
        o = jax.lax.map(jax.remat(lambda args: chunk_attn(*args)), (qc, iqs))
        o = o.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, H, dh)
    else:
        o = chunk_attn(qg, q_offset + jnp.arange(Sq)).reshape(B, Sq, H, dh)
    return o


def attn_apply(
    p, x, cfg: ArchConfig, *, positions=None, cache=None, mode=None,
    sliding=None,
):
    """Full attention sub-block: projections + RoPE (+qk-norm) + attention.

    cache: None (training/prefill without cache) or dict(k, v, pos) for
    decode; when given, k/v are written at `pos` and attended with kv_len.
    ``sliding``: this layer's kind in a `layer_types` model (a traced bool,
    scanned with the layer): the window mask and the plain RoPE table when
    true, full attention and the full layers' table (YaRN) when false.
    Returns (out, new_cache).
    """
    B, S, D = x.shape
    H, KV, dh = cfg.n_heads, cfg.n_kv, cfg.head_dim
    # projections stay in compute dtype end-to-end: the MXU accumulates in
    # f32 internally, and an explicit f32 output materializes a 2x-size
    # tensor per projection before the convert (§Perf iteration 4)
    xc = x.astype(_ct(cfg))
    q = jnp.einsum("bsd,dh->bsh", xc, p["wq"].astype(_ct(cfg))).reshape(B, S, H, dh)
    k = jnp.einsum("bsd,dh->bsh", xc, p["wk"].astype(_ct(cfg))).reshape(B, S, KV, dh)
    v = jnp.einsum("bsd,dh->bsh", xc, p["wv"].astype(_ct(cfg))).reshape(B, S, KV, dh)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    if sliding is not None:
        freqs, scale = rope_table(cfg, sliding)
        q = rope_apply(q, positions, cfg.rope_theta, freqs, scale)
        k = rope_apply(k, positions, cfg.rope_theta, freqs, scale)
    elif cfg.attn != "bidir":  # encoders here use absolute embeddings instead
        q = rope_apply(q, positions, cfg.rope_theta)
        k = rope_apply(k, positions, cfg.rope_theta)

    def _expand(t, hook=True):
        # KV-head replication for TP (cfg.expand_kv): (B,S,KV,dh)->(B,S,H,dh)
        if cfg.expand_kv and t.shape[2] != H:
            t = jnp.repeat(t, H // t.shape[2], axis=2)
        # hook only fresh tensors — cached k/v carry cache_seq sharding that
        # a heads-only constraint would destroy
        return _qkv_hook(t) if hook else t

    q = _qkv_hook(q)
    new_cache = None
    if cache is None:
        o = multihead_attention(q, _expand(k), _expand(v), cfg, mode=mode,
                                sliding=sliding)
    else:
        # Ring-buffer cache: slot = pos % S_cache (for full attention the
        # cache is sized to max_len so slot == pos; for SWA it is sized to
        # the window and wraps).  Per-slot absolute positions drive masking.
        pos = cache["pos"]  # scalar int32: tokens already generated
        s_cache = cache["k"].shape[1]
        slot = pos % s_cache
        ck = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0)
        )
        cv = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0)
        )
        kv_pos = jax.lax.dynamic_update_slice(
            cache["kv_pos"], pos + jnp.arange(S, dtype=jnp.int32), (slot,)
        )
        o = multihead_attention(
            q, _expand(ck.astype(q.dtype), hook=False),
            _expand(cv.astype(q.dtype), hook=False), cfg,
            q_offset=pos, mode=mode, kv_positions=kv_pos, sliding=sliding,
        )
        new_cache = {"k": ck, "v": cv, "kv_pos": kv_pos, "pos": pos + S}
    o = o.reshape(B, S, H * dh)
    out = jnp.einsum("bsh,hd->bsd", o, p["wo"].astype(_ct(cfg)))
    return out.astype(x.dtype), new_cache


# ---------------------------------------------------------------------------
# MLP (swiglu / geglu / sq_relu / gelu) + spiking variant
# ---------------------------------------------------------------------------

def mlp_init(key, cfg: ArchConfig, d_ff=None) -> dict:
    D, F = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.spiking_ffn:
        # Spiking FFN: two GEMMs only (no gate), whatever the host arch's
        # activation is.  LTH pruning happens ONCE, here: the stored params
        # carry hard zeros for their whole lifetime (train, serve,
        # checkpoints) and forward passes never re-prune — the load-time
        # weight join plans of the dual-sparse serving path are built from
        # exactly these zeros.  The pattern is rounded to the plan's MXU
        # block grid (whole zero blocks the join can skip) while keeping the
        # exact element density; non-divisible shapes fall back to
        # unstructured hard zeros.
        from repro.core.snn_layers import prune_by_magnitude
        from repro.kernels.join_plan import pick_plan_blocks

        p = {
            "wu": dense_init(ks[0], (D, F), _dt(cfg)),
            "wd": dense_init(ks[1], (F, D), _dt(cfg)),
        }
        if cfg.spiking_weight_density < 1.0:
            d = cfg.spiking_weight_density
            for name in ("wu", "wd"):
                K, N = p[name].shape
                bk, bn = pick_plan_blocks(K, N)
                block = (bk, bn) if (K % bk == 0 and N % bn == 0) else None
                p[name] = prune_by_magnitude(p[name], d, block=block)
        return p
    if cfg.act in ("swiglu", "geglu"):
        return {
            "wg": dense_init(ks[0], (D, F), _dt(cfg)),
            "wu": dense_init(ks[1], (D, F), _dt(cfg)),
            "wd": dense_init(ks[2], (F, D), _dt(cfg)),
        }
    return {
        "wu": dense_init(ks[0], (D, F), _dt(cfg)),
        "wd": dense_init(ks[1], (F, D), _dt(cfg)),
    }


def mlp_axes(cfg: ArchConfig) -> dict:
    if cfg.act in ("swiglu", "geglu") and not cfg.spiking_ffn:
        return {
            "wg": ("d_model", "d_ff"),
            "wu": ("d_model", "d_ff"),
            "wd": ("d_ff", "d_model"),
        }
    return {"wu": ("d_model", "d_ff"), "wd": ("d_ff", "d_model")}


# Spiking-FFN execution mode: "train" keeps the surrogate-gradient float
# path (differentiable); "infer" routes through the packed uint32 FTP path
# (identical forward values — spikes are exactly {0, 1} either way and both
# paths lower to the same folded (T*M, K) contraction).  The serving engine
# flips this so SNN layers carry packed spike words during engine steps.
_spiking_ffn_mode = "train"


def set_spiking_ffn_mode(mode: str) -> None:
    if mode not in ("train", "infer"):
        raise ValueError(f"unknown spiking FFN mode {mode!r}")
    global _spiking_ffn_mode
    _spiking_ffn_mode = mode


def get_spiking_ffn_mode() -> str:
    return _spiking_ffn_mode


def attach_spiking_ffn_plans(
    params: dict, cfg: ArchConfig, model_shards: int = 1
) -> dict:
    """Load-time step of the dual-sparse serving path for the arch zoo.

    Walks the param tree, finds every spiking-FFN weight pair (stacked
    (L, K, N) for scanned layer stacks, or plain (K, N)), asserts the
    prune-once density contract, and attaches per-layer `WeightJoinPlan`s
    (``plan_in`` / ``plan_out``).  Stacked layers get `stack_plans`-padded
    plans with a leading layer axis, so they scan with `jax.lax.scan`
    exactly like the weights.  Spiking experts ((L, E, K, N) under a
    ``moe`` node with its ``router``) get one plan per (layer, expert),
    stacked over the expert axis and then the layer axis: a scanned layer
    sees an (E, ...) plan, which the grouped BSR call indexes by each row
    tile's expert.  Host work happens once here; every subsequent forward
    is device-only.

    ``model_shards > 1`` (mesh serving): each per-layer plan is column-split
    into that many self-contained slabs (`join_plan.shard_plan`) stacked on
    an extra axis — innermost, so a scanned layer stack slices to
    (shards, ...) per layer.  `serve.sharding.place_plans` then deals the
    slab axis out over the mesh's `model` axis, and the BSR kernel entry
    (`ops.dispatch` with a dual-sparse policy) routes such plans through
    its shard_map entry.
    """
    if not cfg.spiking_ffn:
        return params
    import numpy as np

    from repro.core.snn_layers import assert_weight_density
    from repro.kernels.join_plan import (
        build_sharded_weight_plan,
        build_weight_plan,
        shard_plan,
        stack_plans,
    )

    ct = _ct(cfg)

    def one_plan(w2d):
        if model_shards > 1:
            return shard_plan(
                build_sharded_weight_plan(w2d, model_shards), model_shards
            )
        return build_weight_plan(w2d)

    def stacked(w):
        if w.ndim == 2:
            return one_plan(w)
        return stack_plans([stacked(w[i]) for i in range(w.shape[0])])

    def plans_for(w):
        # payload carries the compute-dtype cast the apply path uses, so the
        # kernel contracts bit-identical values to the dense jnp path
        return stacked(np.asarray(jnp.asarray(w).astype(ct)))

    def prepare(node):
        wu, wd = node["wu"], node["wd"]
        if "router" in node and model_shards > 1:
            raise NotImplementedError(
                "spiking experts have no model-sharded plans; serve them "
                "on a mesh without a model axis")
        if cfg.spiking_weight_density < 1.0:
            assert_weight_density(wu, cfg.spiking_weight_density)
            assert_weight_density(wd, cfg.spiking_weight_density)
        return dict(node, plan_in=plans_for(wu), plan_out=plans_for(wd))

    return _map_spiking_ffns(params, prepare)


def _map_spiking_ffns(params: dict, fn) -> dict:
    """``params`` with ``fn`` applied to every spiking-FFN node: a ``wu``/
    ``wd`` pair with no gate, the dense ``mlp`` or the spiking experts of a
    ``moe`` node (its ``router`` rides along)."""

    def walk(node):
        if isinstance(node, dict):
            if {"wu", "wd"} <= node.keys() and "wg" not in node:
                return fn(node)
            return {k: walk(v) for k, v in node.items()}
        return node

    return walk(params)


def derive_draft_params(params: dict, cfg: ArchConfig, density: float) -> dict:
    """Second param tree for `ExecutionPolicy.speculation` drafts: every
    spiking-FFN weight pair re-pruned to ``density`` (< the target's
    ``cfg.spiking_weight_density``), all other leaves SHARED with the target
    tree (same arrays — the draft is the same model under a sparser plan,
    and the extra host memory is just the pruned FFN copies).

    Returns a plan-free tree; the caller attaches the draft's own
    `WeightJoinPlan`s with the ordinary `attach_spiking_ffn_plans` (which
    re-asserts the density contract — a further-pruned weight always
    satisfies the target bound).
    """
    if not cfg.spiking_ffn:
        raise ValueError("draft weight pruning needs a spiking-FFN arch")
    from repro.kernels.join_plan import prune_to_density

    def prune(w):
        w = jnp.asarray(w)
        if w.ndim == 2:
            return jnp.asarray(prune_to_density(w, density))
        import numpy as np

        flat = w.reshape((-1,) + w.shape[-2:])
        return jnp.asarray(
            np.stack([prune_to_density(m, density) for m in flat])
        ).reshape(w.shape)

    def draft(node):
        out = {k: v for k, v in node.items()
               if k not in ("plan_in", "plan_out")}
        out["wu"] = prune(node["wu"])
        out["wd"] = prune(node["wd"])
        return out

    return _map_spiking_ffns(params, draft)


def mlp_apply(p, x, cfg: ArchConfig):
    xc = x.astype(_ct(cfg))
    if cfg.spiking_ffn:
        # Paper technique (DESIGN.md §4): dual-sparse spiking FFN under the
        # FTP dataflow, surrogate-gradient differentiable.  Weights carry
        # their LTH hard zeros from mlp_init; in packed-inference mode a
        # serving-time `attach_spiking_ffn_plans` adds per-layer join plans
        # that route both GEMMs through the dual-sparse BSR kernel (via
        # `ops.dispatch` under the engine's ExecutionPolicy).
        from repro.core.snn_layers import SpikingConfig, spiking_ffn_apply

        scfg = SpikingConfig(
            T=cfg.spiking_T, weight_density=cfg.spiking_weight_density
        )
        wu, wd = p["wu"], p["wd"]
        plans = None
        if _spiking_ffn_mode == "infer" and "plan_in" in p:
            plans = (p["plan_in"], p["plan_out"])
        y = spiking_ffn_apply(
            {"w_in": wu.astype(_ct(cfg)), "w_out": wd.astype(_ct(cfg))},
            xc, scfg, mode=_spiking_ffn_mode,
            use_kernel=jax.default_backend() == "tpu",
            plans=plans,
        )
        return y.astype(x.dtype)
    with jax.named_scope("ffn.up"):
        if cfg.act == "swiglu":
            h = jax.nn.silu(xc @ p["wg"].astype(_ct(cfg))) * (xc @ p["wu"].astype(_ct(cfg)))
        elif cfg.act == "geglu":
            h = jax.nn.gelu(xc @ p["wg"].astype(_ct(cfg))) * (xc @ p["wu"].astype(_ct(cfg)))
        elif cfg.act == "sq_relu":
            h = jnp.square(jax.nn.relu(xc @ p["wu"].astype(_ct(cfg))))
        elif cfg.act == "gelu":
            h = jax.nn.gelu(xc @ p["wu"].astype(_ct(cfg)))
        else:
            raise ValueError(cfg.act)
    with jax.named_scope("ffn.down"):
        return (h @ p["wd"].astype(_ct(cfg))).astype(x.dtype)


# ---------------------------------------------------------------------------
# MoE: dense experts (top-k router, capacity-gather dispatch — EP-shardable
# on `experts`) and spiking experts (drop-free, grouped BSR)
# ---------------------------------------------------------------------------

def moe_init(key, cfg: ArchConfig) -> dict:
    """Router and expert weights.  Spiking experts (``cfg.spiking_ffn``)
    are the spiking FFN's two matrices per expert, pruned once here per
    expert as `mlp_init` prunes the dense one."""
    D, F, E = cfg.d_model, cfg.expert_width, cfg.n_experts
    ks = jax.random.split(key, 4)
    glu = cfg.act in ("swiglu", "geglu") and not cfg.spiking_ffn
    p = {
        "router": dense_init(ks[0], (D, E), jnp.float32),
        "wu": dense_init(ks[1], (E, D, F), _dt(cfg), fan_in=D),
        "wd": dense_init(ks[2], (E, F, D), _dt(cfg), fan_in=F),
    }
    if glu:
        p["wg"] = dense_init(ks[3], (E, D, F), _dt(cfg), fan_in=D)
    if cfg.spiking_ffn and cfg.spiking_weight_density < 1.0:
        from repro.core.snn_layers import prune_by_magnitude
        from repro.kernels.join_plan import pick_plan_blocks

        d = cfg.spiking_weight_density
        for name in ("wu", "wd"):
            K, N = p[name].shape[1:]
            bk, bn = pick_plan_blocks(K, N)
            block = (bk, bn) if (K % bk == 0 and N % bn == 0) else None
            p[name] = jax.vmap(
                lambda w: prune_by_magnitude(w, d, block=block))(p[name])
    return p


def moe_axes(cfg: ArchConfig) -> dict:
    ax = {
        "router": ("d_model", None),
        "wu": ("experts", "d_model", "d_ff"),
        "wd": ("experts", "d_ff", "d_model"),
    }
    if cfg.act in ("swiglu", "geglu") and not cfg.spiking_ffn:
        ax["wg"] = ("experts", "d_model", "d_ff")
    return ax


def rows_independent(cfg: ArchConfig) -> bool:
    """Whether each row of a batch is computed from that row alone.  Only
    capacity routing (`moe_apply`'s dense experts) couples rows: it drops
    the tokens past an expert's capacity, and which those are depends on
    the whole batch.  Spiking experts (`spiking_moe_apply`) drop none."""
    return not cfg.n_experts or cfg.spiking_ffn


def _route(router, xt, n_experts: int, k: int):
    """Softmax over the router's logits, top ``k``, gates renormalised;
    the Switch load-balancing loss beside.  Float32 at full precision:
    the router is tiny, and a rounded logit can swap an expert."""
    logits = jnp.dot(xt.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eidx = jax.lax.top_k(probs, k)                     # (T, k)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    top1 = jax.nn.one_hot(eidx[:, 0], n_experts, dtype=jnp.float32)
    aux = n_experts * jnp.sum(jnp.mean(top1, axis=0) * jnp.mean(probs, axis=0))
    return gate, eidx, aux


def spiking_moe_apply(p, x, cfg: ArchConfig):
    """Spiking dual-sparse experts with drop-free top-k routing.

    x: (B, S, D).  Each token goes to its ``top_k`` experts (softmax, top
    k, gates renormalised) and gets the gate-weighted sum of their spiking
    FFNs (direct encode, W_in, LIF, W_out, rate decode).  Every routed
    (token, expert) pair is computed, whatever the skew, so a row's output
    depends on that row alone (`rows_independent`).

    Serving (packed inference with join plans attached) sorts the routed
    rows by expert, pads each expert's rows to the kernel's row tile, and
    runs each GEMM as one grouped BSR call over all experts (`ops.dispatch`
    with ``groups``); otherwise every expert runs the float path on every
    token and the gates select.  Named scopes inside ``ffn``: ``ffn.route``
    (router, top k, sort and pad), ``ffn.encode``, ``ffn.up``,
    ``ffn.down``, ``ffn.combine`` (un-sort, gate-weighted sum)."""
    B, S, D = x.shape
    xt = x.reshape(B * S, D)
    with jax.named_scope("ffn.route"):
        gate, eidx, aux = _route(p["router"], xt, cfg.n_experts, cfg.top_k)
    if _spiking_ffn_mode == "infer" and "plan_in" in p:
        y = _experts_grouped(p, xt, gate, eidx, cfg)
    else:
        y = _experts_dense(p, xt, gate, eidx, cfg)
    return y.reshape(B, S, D).astype(x.dtype), aux


def _spiking_cfg(cfg: ArchConfig):
    from repro.core.snn_layers import SpikingConfig

    return SpikingConfig(T=cfg.spiking_T,
                         weight_density=cfg.spiking_weight_density)


def _combine(y_rows, gate):
    """(T, k, D) expert outputs of each token's routed pairs -> (T, D):
    the gate-weighted sum, over k in routing order for every row."""
    with jax.named_scope("ffn.combine"):
        return jnp.sum(y_rows.astype(jnp.float32) * gate[..., None], axis=1)


def _experts_dense(p, xt, gate, eidx, cfg: ArchConfig):
    """Every expert over every token (the differentiable float path, and
    packed inference without plans); the gates pick each token's k."""
    from repro.core.snn_layers import spiking_ffn_apply

    ct = _ct(cfg)
    xc = xt.astype(ct)

    def one(wu, wd):
        return spiking_ffn_apply(
            {"w_in": wu.astype(ct), "w_out": wd.astype(ct)}, xc,
            _spiking_cfg(cfg), mode=_spiking_ffn_mode,
            use_kernel=jax.default_backend() == "tpu")

    ye = jax.vmap(one)(p["wu"], p["wd"])                     # (E, T, D)
    return _combine(ye[eidx, jnp.arange(xt.shape[0])[:, None]], gate)


def _experts_grouped(p, xt, gate, eidx, cfg: ArchConfig):
    """The serving path: routed rows sorted and padded by expert, both
    GEMMs one grouped BSR call each (LIF fused into the up projection,
    the rate decode into the down projection)."""
    from repro.core.lif import direct_encode
    from repro.core.packing import pack_spikes
    from repro.kernels import ops
    from repro.serve.policy import PACKED_DUAL

    sc = _spiking_cfg(cfg)
    n, d = xt.shape
    with jax.named_scope("ffn.route"):
        bm = ops.grouped_row_block(eidx.size, cfg.n_experts)
        src, dest, groups = ops.group_rows(eidx, cfg.n_experts, bm)
    with jax.named_scope("ffn.encode"):
        words = pack_spikes(direct_encode(xt.astype(_ct(cfg)), sc.T,
                                          v_th=sc.v_th, tau=sc.tau))
        words = jnp.concatenate([words, jnp.zeros((1, d), words.dtype)])[src]
    with jax.named_scope("ffn.up"):
        hidden = ops.dispatch(
            words, p["plan_in"], PACKED_DUAL, sc.T, fuse_lif=True,
            v_th=sc.v_th, tau=sc.tau, n_out=p["wu"].shape[-1], groups=groups)
    with jax.named_scope("ffn.down"):
        out = ops.dispatch(hidden, p["plan_out"], PACKED_DUAL, sc.T,
                           fuse_lif=False, n_out=d, groups=groups)
    return _combine(out[dest], gate)


def moe_apply(p, x, cfg: ArchConfig):
    """Top-k token-choice MoE with capacity-based gather dispatch.

    x: (B, S, D).  Dispatch/combine are dense gathers/scatters of shape
    (E, C, D) so the expert dimension is shardable (EP) and everything lowers
    to einsums (MXU) + all-to-alls under GSPMD.
    """
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    xt = x.reshape(B * S, D)
    T = B * S
    C = max(1, int(T * K * cfg.capacity_factor / E))

    logits = (xt.astype(jnp.float32) @ p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eidx = jax.lax.top_k(probs, K)                    # (T, K)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)

    # position of each (token, k) within its expert's capacity buffer
    onehot = jax.nn.one_hot(eidx, E, dtype=jnp.int32)       # (T, K, E)
    flat = onehot.reshape(T * K, E)
    pos_flat = jnp.cumsum(flat, axis=0) - flat              # (T*K, E)
    pos = jnp.sum(pos_flat.reshape(T, K, E) * onehot, axis=-1)  # (T, K)
    keep = pos < C

    # dispatch: (E, C, D)
    disp = jnp.zeros((E, C, D), dtype=x.dtype)
    e_safe = jnp.where(keep, eidx, 0)
    p_safe = jnp.where(keep, pos, 0)
    contrib = jnp.where(keep[..., None], xt[:, None, :], 0).astype(x.dtype)
    disp = disp.at[e_safe, p_safe].add(contrib)

    # expert FFNs: (E, C, D) x (E, D, F)
    ct = _ct(cfg)
    h_u = jnp.einsum("ecd,edf->ecf", disp.astype(ct), p["wu"].astype(ct))
    if "wg" in p:
        act = jax.nn.silu if cfg.act == "swiglu" else jax.nn.gelu
        h_g = jnp.einsum("ecd,edf->ecf", disp.astype(ct), p["wg"].astype(ct))
        h = act(h_g) * h_u
    else:
        h = jnp.square(jax.nn.relu(h_u)) if cfg.act == "sq_relu" else jax.nn.gelu(h_u)
    y_e = jnp.einsum("ecf,efd->ecd", h, p["wd"].astype(ct))  # (E, C, D)

    # combine: gather each token's K expert outputs, weight by gates
    y_tk = y_e[e_safe, p_safe]                               # (T, K, D)
    y = jnp.sum(
        y_tk * (gate * keep).astype(y_tk.dtype)[..., None], axis=1
    )
    # aux load-balancing loss (Switch): E * sum_e f_e * p_e
    f = jnp.mean(jnp.sum(onehot[:, 0], axis=0) / T)  # fraction to top-1
    me = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(jnp.mean(jax.nn.one_hot(eidx[:, 0], E, dtype=jnp.float32), axis=0) * me)
    return y.reshape(B, S, D).astype(x.dtype), aux
