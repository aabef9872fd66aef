"""Analytic operation and byte counts, from the configuration's shapes.

They count the work the model needs, whatever implements it, so a later
kernel that does the same work another way reads against the same count:

* FFN: ``2 * T * density * K * N`` per token per matrix (both matrices);
* attention: the four projections, plus ``4 * ctx * heads * head_dim``
  per token per layer, ``ctx`` the positions the token attends (causal);
* LM head: ``2 * hidden * vocab`` only for tokens whose logits are
  computed: the last prompt token of a prefill row, each decode token;
* BSR kernel bytes: the bfloat16 nonzero weight payload, the packed
  uint32 spike words in, and the outputs at their dtype (packed uint32
  spikes and the float32 potential for the fused-LIF up projection,
  float32 full sums for all T and the potential for the down projection).
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Shapes:
    D: int
    F: int
    V: int
    L: int
    H: int
    KV: int
    dh: int
    T: int
    rho: float

    @classmethod
    def of(cls, conf: dict) -> "Shapes":
        sp = conf["spiking"]
        return cls(conf["hidden_size"], conf["intermediate_size"],
                   conf["vocab_size"], conf["num_hidden_layers"],
                   conf["num_attention_heads"], conf["num_key_value_heads"],
                   conf["head_dim"], sp["T"], sp["weight_density"])


def proj_flops(s: Shapes) -> float:
    """q, k, v and o projections, per token per layer."""
    return 2.0 * s.D * s.H * s.dh + 4.0 * s.D * s.KV * s.dh + 2.0 * s.H * s.dh * s.D


def ffn_flops(s: Shapes) -> float:
    """Both spiking-FFN GEMMs, per token per layer."""
    return 2 * (2.0 * s.T * s.rho * s.D * s.F)


def head_flops(s: Shapes) -> float:
    return 2.0 * s.D * s.V


def prefill_flops(s: Shapes, prompt_len: int, rows: int) -> float:
    """A prefill of ``rows`` prompts of ``prompt_len`` tokens each."""
    P = prompt_len
    attn = 4.0 * s.H * s.dh * P * (P + 1) / 2
    per_row = s.L * (P * (proj_flops(s) + ffn_flops(s)) + attn) + head_flops(s)
    return rows * per_row


def decode_flops(s: Shapes, ctxs) -> float:
    """One decode step of rows whose new token attends ``ctx`` positions."""
    return sum(s.L * (proj_flops(s) + ffn_flops(s) + 4.0 * s.H * s.dh * c)
               + head_flops(s) for c in ctxs)


def bsr_call(s: Shapes, rows: int, which: str) -> tuple[float, float]:
    """(operations, bytes) of one BSR kernel call over ``rows`` rows:
    ``which`` is ``up`` (hidden x d_ff, fused LIF) or ``down``."""
    K, N = (s.D, s.F) if which == "up" else (s.F, s.D)
    flops = 2.0 * s.T * s.rho * rows * K * N
    payload = s.rho * K * N * 2.0
    spikes_in = rows * K * 4.0
    if which == "up":
        out = rows * N * 4.0 + rows * N * 4.0
    else:
        out = s.T * rows * N * 4.0 + rows * N * 4.0
    return flops, payload + spikes_in + out


def roofline_s(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The least time the chip needs, and which bound sets it."""
    t_c = flops / peak["bf16_flops"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
