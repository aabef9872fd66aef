"""Plain float32 reference of the decoder LM with spiking dual-sparse
experts and mixed sliding/full attention (Mellum2's block).

Written from the model's equations, not from the program: no kernel, no
KV cache, no batching, no packed spikes.  One request at a time, its
whole sequence at once, every matrix product at
``jax.lax.Precision.HIGHEST`` so that a TPU does not round float32
operands to bfloat16.

Per layer l (pre-norm), of kind ``layer_types[l]``:

    h  = RMSNorm(x) * (1 + ln1)
    q, k, v = h Wq, h Wk, h Wv            (GQA: head i reads kv head i // G)
    q, k = RoPE_l(q), RoPE_l(k)           (half-split rotation)
    x += softmax(q k^T / sqrt(dh) + mask_l) v Wo
    h2 = RMSNorm(x) * (1 + ln2)
    x += sum_{e in top8(p)} g_e SpikingFFN_e(h2)

    mask_l: causal; a sliding layer also hides key j from query i unless
            j > i - sliding_window.
    RoPE_l: sliding layers the plain table, theta^(-2i/dh); full layers
            YaRN's, inverse frequencies and attention factor as Hugging
            Face ``transformers``' ``_compute_yarn_parameters`` defines
            them with ``truncate`` true (`_yarn`), cos and sin both scaled
            by the attention factor.
    p = softmax(h2 W_router); top8 the 8 largest; g = p / sum of the 8.

SpikingFFN_e (the paper's T-HFF; LIF with hard reset, `core/lif.py`'s
equations), over the tokens routed to expert e:

    LIF over t = 0..T-1 of currents O[t]:
        X[t] = O[t] + U[t-1];  S[t] = 1[X[t] > v_th];  U[t] = tau X[t] (1 - S[t])
    S_in  = LIF(O[t] = h2 for every t)         (direct encoding)
    S_hid = LIF(O[t] = S_in[t] W_in_e)
    out   = mean_t(S_hid[t] W_out_e)           (rate decoding)

Each expert runs over the tokens routed to it, in blocks of `BLOCK` rows,
never over all tokens; attention runs one kv head's group of query heads
at a time.  Departures from the published model are listed under
``assumed`` in the configuration file; the largest is the spiking FFN in
place of each SwiGLU expert.

``variant`` puts a broken reference in the program's place, for the
check's negative readings: ``fp8`` is the control, every matrix-product
operand rounded to float8 e4m3 with one scale per tensor (the precision
below the bfloat16 that the configuration states for compute); ``top7``
drops each token's 8th expert; ``capacity_1.25`` routes as capacity MoE
does at factor 1.25 (per expert, tokens past ``int(1.25 * tokens * 8 /
64)`` in token order are dropped); ``no_window`` lets the sliding layers
attend to the whole prefix; ``ffn_zeroed`` loses the experts' output.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0
BLOCK = 512          # rows of one expert computed at once
CAPACITY = 1.25      # the capacity_1.25 variant's factor

VARIANTS = ("fp8", "top7", "capacity_1.25", "no_window", "ffn_zeroed")


def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + scale)


def _yarn(dh, theta, factor, original_max_pos, beta_fast, beta_slow):
    """YaRN's inverse frequencies, as ``_compute_yarn_parameters``
    (``truncate`` true): interpolated by ``factor`` below ``beta_slow``
    rotations over the original context, kept above ``beta_fast``, a
    linear ramp between."""

    def dim(rot):
        return (dh * math.log(original_max_pos / (rot * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim(beta_fast)), 0)
    high = min(math.ceil(dim(beta_slow)), dh - 1)
    if low == high:
        high += 0.001
    pos_freqs = theta ** (np.arange(0, dh, 2, dtype=np.float32) / dh)
    ramp = np.clip((np.arange(dh // 2, dtype=np.float32) - low)
                   / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp
    return (1.0 / (factor * pos_freqs) * (1.0 - keep)
            + 1.0 / pos_freqs * keep).astype(np.float32)


def rope_tables(conf):
    """((dh/2,) inverse frequencies, cos/sin factor) by layer kind."""
    dh = conf["head_dim"]
    rp = conf["rope_parameters"]
    plain = rp["sliding_attention"]["rope_theta"] ** (
        -np.arange(0, dh, 2, dtype=np.float32) / dh)
    y = rp["full_attention"]
    return {"sliding_attention": (plain.astype(np.float32), 1.0),
            "full_attention": (_yarn(dh, y["rope_theta"], y["factor"],
                                     y["original_max_position_embeddings"],
                                     y["beta_fast"], y["beta_slow"]),
                               y["attention_factor"])}


def _rope(x, pos, inv_freq, factor):
    half = x.shape[-1] // 2
    ang = pos.astype(jnp.float32)[:, None] * inv_freq
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
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


def _attention(conf, q, x, a, inv_freq, factor, sliding):
    S = x.shape[0]
    H, KV = conf["num_attention_heads"], conf["num_key_value_heads"]
    dh, G = conf["head_dim"], H // KV
    mm = functools.partial(jnp.matmul, precision=HIGHEST)
    pos = jnp.arange(S)
    qh = _rope(mm(q(x), q(a["wq"])).reshape(S, H, dh), pos, inv_freq, factor)
    kh = _rope(mm(q(x), q(a["wk"])).reshape(S, KV, dh), pos, inv_freq, factor)
    vh = mm(q(x), q(a["wv"])).reshape(S, KV, dh)
    mask = pos[:, None] >= pos[None, :]
    if sliding:
        mask &= pos[None, :] > pos[:, None] - conf["sliding_window"]
    qg = q(qh).reshape(S, KV, G, dh).transpose(1, 2, 0, 3)   # (KV, G, S, dh)
    kg, vg = q(kh).transpose(1, 0, 2), q(vh).transpose(1, 0, 2)

    def group(args):                                         # one kv head
        qi, ki, vi = args
        s = jnp.einsum("gqd,kd->gqk", qi, ki, precision=HIGHEST) * dh ** -0.5
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("gqk,kd->gqd", q(p), vi, precision=HIGHEST)

    o = jax.lax.map(group, (qg, kg, vg)).transpose(2, 0, 1, 3)
    return mm(q(o.reshape(S, H * dh)), q(a["wo"]))


def _route(conf, q, h2, router, variant):
    """(gates (S, k) with dropped pairs at 0, experts (S, k))."""
    E, k = conf["num_experts"], conf["num_experts_per_tok"]
    logits = jnp.matmul(q(h2), q(router), precision=HIGHEST)
    probs = jax.nn.softmax(logits, -1)
    gate, eidx = jax.lax.top_k(probs, k)
    gate = gate / jnp.sum(gate, -1, keepdims=True)
    if variant == "top7":
        gate = gate.at[:, k - 1].set(0.0)
    elif variant == "capacity_1.25":
        S = h2.shape[0]
        cap = max(1, int(S * k * CAPACITY / E))
        onehot = jax.nn.one_hot(eidx.reshape(-1), E, dtype=jnp.int32)
        seen = jnp.sum((jnp.cumsum(onehot, 0) - onehot) * onehot, -1)
        gate = jnp.where(seen.reshape(S, k) < cap, gate, 0.0)
    return gate, eidx


def _experts(conf, q, h2, moe, valid, variant):
    """Gate-weighted sum of each token's routed experts, and the FFN spike
    rates (input over the sequence's tokens, hidden over its routed
    pairs)."""
    S, D = h2.shape
    E, k = conf["num_experts"], conf["num_experts_per_tok"]
    sp = conf["spiking"]
    T, v_th, tau = sp["T"], sp["v_th"], sp["tau"]
    gate, eidx = _route(conf, q, h2, moe["router"], variant)
    flat = eidx.reshape(-1)
    order = jnp.argsort(flat, stable=True)                   # pairs by expert
    tok = order // k
    w = gate.reshape(-1)[order]
    counts = jnp.bincount(flat, length=E)
    start = jnp.cumsum(counts) - counts
    s_in = _lif(jnp.broadcast_to(h2, (T, S, D)), v_th, tau)  # (T, S, D)

    def expert(e, carry):
        def block(b, carry):
            y, fired, pairs = carry
            at = b * BLOCK + jnp.arange(BLOCK)
            live = at < counts[e]
            j = jnp.minimum(start[e] + at, S * k - 1)
            t = tok[j]
            hid = _lif(jnp.einsum("tsd,df->tsf", s_in[:, t], q(moe["wu"][e]),
                                  precision=HIGHEST), v_th, tau)
            out = jnp.mean(jnp.einsum("tsf,fd->tsd", hid, q(moe["wd"][e]),
                                      precision=HIGHEST), 0)
            y = y.at[t].add(jnp.where(live, w[j], 0.0)[:, None] * out)
            mine = live & (valid[t] > 0)
            fired += jnp.sum(jnp.mean(hid, (0, 2)) * mine)
            return y, fired, pairs + jnp.sum(mine)

        n_blocks = (counts[e] + BLOCK - 1) // BLOCK
        return jax.lax.fori_loop(0, n_blocks, block, carry)

    y, fired, pairs = jax.lax.fori_loop(
        0, E, expert, (jnp.zeros((S, D), jnp.float32), jnp.float32(0.0),
                       jnp.int32(0)))
    if variant == "ffn_zeroed":
        y = jnp.zeros_like(y)
    rate_in = jnp.sum(jnp.mean(s_in, (0, 2)) * valid) / jnp.sum(valid)
    return y, jnp.stack([rate_in, fired / jnp.maximum(pairs, 1)])


@functools.lru_cache(maxsize=None)
def _forward(conf_key: str, variant: str | None, n_rows: int):
    conf = json.loads(conf_key)
    q = _fp8 if variant == "fp8" else (lambda t: t)
    eps = conf["rms_norm_eps"]
    tables = rope_tables(conf)
    kinds = conf["layer_types"][: conf["num_hidden_layers"]]

    def forward(w, tokens, start, n_valid):
        x = w["embed"].astype(jnp.float32)[tokens]
        valid = (jnp.arange(tokens.shape[0]) < n_valid).astype(jnp.float32)
        layers = jax.tree.map(lambda a: a.astype(jnp.float32), w["layers"])
        rates = []
        for l, kind in enumerate(kinds):
            lp = jax.tree.map(lambda a: a[l], layers)
            inv_freq, factor = tables[kind]
            sliding = kind == "sliding_attention" and variant != "no_window"
            x = x + _attention(conf, q, _rms(x, lp["ln1"], eps), lp["attn"],
                               jnp.asarray(inv_freq), factor, sliding)
            y, r = _experts(conf, q, _rms(x, lp["ln2"], eps), lp["moe"],
                            valid, variant)
            x = x + y
            rates.append(r)
        rows = jax.lax.dynamic_slice_in_dim(x, start, n_rows, axis=0)
        rows = _rms(rows, w["final_norm"].astype(jnp.float32), eps)
        logits = jnp.matmul(q(rows), q(w["lm_head"].astype(jnp.float32)),
                            precision=HIGHEST)
        return logits, jnp.mean(jnp.stack(rates), 0)

    return jax.jit(forward)


def served_logits(conf: dict, w: dict, prompt, served, seq_len: int,
                  n_rows: int, variant: str | None = None):
    """Logits of every position that chose a served token.

    Runs the whole sequence ``prompt + served[:-1]``, zero-padded at the
    end to ``seq_len`` (causal: padding changes no earlier position, and
    an expert's output for a token depends on that token alone), and
    returns ((len(served), vocab) float32 logits whose row i is the
    distribution that chose ``served[i]``, (FFN input, FFN hidden) spike
    rates: the share of neuron-timesteps that fire, the input over the
    sequence's own tokens, the hidden over their routed (token, expert)
    pairs, averaged over the layers).  ``n_rows`` >= len(served) fixes the
    compiled shape.  The ``capacity_1.25`` variant counts the padding
    positions too, as a batch of that shape would."""
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
