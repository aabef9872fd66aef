"""Plain float32 reference of the decoder LM with spiking FFNs.

Written from the model's equations, not from the program: no kernel, no
KV cache, no batching, no packed spikes.  One request at a time, its
whole sequence at once, every matrix product at
``jax.lax.Precision.HIGHEST`` so that a TPU does not round float32
operands to bfloat16.

Per layer (pre-norm, as Mistral and Qwen3 publish it):

    h  = RMSNorm(x) * (1 + ln1)
    q, k, v = h Wq, h Wk, h Wv            (GQA: head i reads kv head i // G)
    q, k = RMSNorm_head(q) * (1 + q_norm), ... (Qwen3's qk_norm only)
    q, k = RoPE(q), RoPE(k)               (half-split rotation, rope_theta)
    x += softmax(q k^T / sqrt(dh) + causal) v Wo
    h2 = RMSNorm(x) * (1 + ln2)
    x += SpikingFFN(h2)

SpikingFFN (the paper's T-HFF; LIF with hard reset, `core/lif.py`'s
equations):

    LIF over t = 0..T-1 of currents O[t]:
        X[t] = O[t] + U[t-1];  S[t] = 1[X[t] > v_th];  U[t] = tau X[t] (1 - S[t])
    S_in  = LIF(O[t] = h2 for every t)         (direct encoding)
    S_hid = LIF(O[t] = S_in[t] W_in)
    out   = mean_t(S_hid[t] W_out)             (rate decoding)

Departures from the published models are listed under ``assumed`` in the
configuration file; the largest is the spiking FFN in place of SwiGLU.

``variant`` puts a broken reference in the program's place, for the
check's negative readings: ``fp8`` is the control, every matrix-product
operand rounded to float8 e4m3 with one scale per tensor (the precision
below the bfloat16 that the configuration states for compute);
``ffn_block_dropped`` drops the first 128-wide block column of every
layer's FFN output and ``ffn_zeroed`` the whole FFN output: faults
confined to the spiking FFN.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + scale)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _lif(currents, v_th, tau):
    u = jnp.zeros_like(currents[0])
    out = []
    for t in range(currents.shape[0]):
        x = currents[t] + u
        s = (x > v_th).astype(jnp.float32)
        u = tau * x * (1.0 - s)
        out.append(s)
    return jnp.stack(out)


def _layer(conf, q, x, lp, valid, variant):
    S, D = x.shape
    H, KV = conf["num_attention_heads"], conf["num_key_value_heads"]
    dh, eps = conf["head_dim"], conf["rms_norm_eps"]
    sp = conf["spiking"]
    mm = functools.partial(jnp.matmul, precision=HIGHEST)
    pos = jnp.arange(S)
    a = lp["attn"]
    h = _rms(x, lp["ln1"], eps)
    qh = mm(q(h), q(a["wq"])).reshape(S, H, dh)
    kh = mm(q(h), q(a["wk"])).reshape(S, KV, dh)
    vh = mm(q(h), q(a["wv"])).reshape(S, KV, dh)
    if conf["qk_norm"]:
        qh = _rms(qh, a["q_norm"], eps)
        kh = _rms(kh, a["k_norm"], eps)
    qh = _rope(qh, pos, conf["rope_theta"])
    kh = _rope(kh, pos, conf["rope_theta"])
    G = H // KV
    kh = jnp.repeat(kh, G, axis=1)
    vh = jnp.repeat(vh, G, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q(qh), q(kh), precision=HIGHEST)
    s = s * dh ** -0.5
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", q(p), q(vh), precision=HIGHEST)
    x = x + mm(q(o.reshape(S, H * dh)), q(a["wo"]))
    h2 = _rms(x, lp["ln2"], eps)
    T, v_th, tau = sp["T"], sp["v_th"], sp["tau"]
    s_in = _lif(jnp.broadcast_to(h2, (T, S, D)), v_th, tau)
    s_hid = _lif(jnp.einsum("tsd,df->tsf", s_in, q(lp["mlp"]["wu"]),
                            precision=HIGHEST), v_th, tau)
    out = jnp.mean(jnp.einsum("tsf,fd->tsd", s_hid, q(lp["mlp"]["wd"]),
                              precision=HIGHEST), 0)
    if variant == "ffn_block_dropped":
        out = out.at[:, : sp["block"][1]].set(0.0)
    elif variant == "ffn_zeroed":
        out = jnp.zeros_like(out)
    rates = jnp.stack([jnp.sum(jnp.mean(s, (0, 2)) * valid)
                       for s in (s_in, s_hid)]) / jnp.sum(valid)
    return x + out, rates


VARIANTS = ("fp8", "ffn_block_dropped", "ffn_zeroed")


@functools.lru_cache(maxsize=None)
def _forward(conf_key: str, variant: str | None, n_rows: int):
    conf = json.loads(conf_key)
    q = _fp8 if variant == "fp8" else (lambda t: t)

    def forward(w, tokens, start, n_valid):
        x = w["embed"].astype(jnp.float32)[tokens]
        valid = (jnp.arange(tokens.shape[0]) < n_valid).astype(jnp.float32)
        layers = jax.tree.map(lambda a: a.astype(jnp.float32), w["layers"])

        def body(x, lp):
            return _layer(conf, q, x, lp, valid, variant)

        x, rates = jax.lax.scan(body, x, layers)
        rows = jax.lax.dynamic_slice_in_dim(x, start, n_rows, axis=0)
        rows = _rms(rows, w["final_norm"].astype(jnp.float32),
                    conf["rms_norm_eps"])
        logits = jnp.matmul(q(rows), q(w["lm_head"].astype(jnp.float32)),
                            precision=HIGHEST)
        return logits, jnp.mean(rates, 0)

    return jax.jit(forward)


def served_logits(conf: dict, w: dict, prompt, served, seq_len: int,
                  n_rows: int, variant: str | None = None):
    """Logits of every position that chose a served token.

    Runs the whole sequence ``prompt + served[:-1]``, zero-padded at the
    end to ``seq_len`` (causal: padding changes no earlier position), and
    returns ((len(served), vocab) float32 logits whose row i is the
    distribution that chose ``served[i]``, (FFN input, FFN hidden) spike
    rates: the share of neuron-timesteps that fire, over the sequence's
    own positions and every layer).  ``n_rows`` >= len(served) fixes the
    compiled shape."""
    if variant is not None and variant not in VARIANTS:
        raise ValueError(f"no reference variant {variant!r}")
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    seq = np.concatenate([prompt, served[:-1]])
    if seq.shape[0] > seq_len or served.shape[0] > n_rows:
        raise ValueError("request longer than the compiled reference shape")
    tokens = np.zeros((seq_len,), np.int32)
    tokens[: seq.shape[0]] = seq
    start = min(prompt.shape[0] - 1, seq_len - n_rows)
    fwd = _forward(json.dumps(conf, sort_keys=True), variant, n_rows)
    logits, rates = fwd(w, jnp.asarray(tokens), jnp.int32(start),
                        jnp.int32(seq.shape[0]))
    off = prompt.shape[0] - 1 - start
    return (np.asarray(logits, np.float32)[off: off + served.shape[0]],
            tuple(float(r) for r in np.asarray(rates)))
