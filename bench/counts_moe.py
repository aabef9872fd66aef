"""Analytic operation and byte counts of the spiking-expert decoder, from
its configuration, in `counts.py`'s convention (a spiking GEMM counts
``2 * T * density * K * N`` per row):

* experts: ``top_k`` routed rows per token, each through both GEMMs of
  its expert (``hidden x moe_intermediate_size`` and back);
* router: ``2 * hidden * num_experts`` per token;
* attention: the four projections, plus ``4 * ctx * heads * head_dim`` per
  token per layer, ``ctx`` the positions the token attends: the whole
  causal prefix on full layers, at most ``sliding_window`` on sliding ones;
* LM head: ``2 * hidden * vocab`` for the last prompt token of a prefill
  row;
* grouped BSR bytes: the bfloat16 payload of the experts that hold rows,
  the packed uint32 spikes in, and the outputs at their dtype (packed
  uint32 spikes from the fused-LIF up projection, float32 rates from the
  down projection).

`counts.Shapes` carries no expert width or top-k, so `expert_conf` finds
the configuration under ``bench/configs/`` whose shapes a record holds.
"""
from __future__ import annotations

import json
from pathlib import Path

import counts

CONFIGS = Path(__file__).resolve().parent / "configs"


def expert_conf(shapes: counts.Shapes) -> dict | None:
    """The expert configuration whose `counts.Shapes` are ``shapes``."""
    for path in sorted(CONFIGS.glob("*.json")):
        conf = json.loads(path.read_text())
        if "num_experts" in conf and counts.Shapes.of(conf) == shapes:
            return conf
    return None


def expert_flops(conf: dict) -> float:
    """Both GEMMs of the routed experts and the router, per token per
    layer."""
    sp = conf["spiking"]
    D, F = conf["hidden_size"], conf["moe_intermediate_size"]
    k, E = conf["num_experts_per_tok"], conf["num_experts"]
    return k * 2 * (2.0 * sp["T"] * sp["weight_density"] * D * F) + 2.0 * D * E


def attended(prompt_len: int, window: int | None) -> float:
    """Positions attended, summed over a prompt's tokens."""
    P = prompt_len
    if window is None or P <= window:
        return P * (P + 1) / 2
    return window * (window + 1) / 2 + (P - window) * window


def prefill_flops(conf: dict, prompt_len: int, rows: int) -> float:
    """A prefill of ``rows`` prompts of ``prompt_len`` tokens each."""
    s = counts.Shapes.of(conf)
    P = prompt_len
    kinds = conf["layer_types"][: s.L]
    per_row = counts.head_flops(s)
    for kind in kinds:
        window = conf["sliding_window"] if kind == "sliding_attention" else None
        per_row += (P * (counts.proj_flops(s) + expert_flops(conf))
                    + 4.0 * s.H * s.dh * attended(P, window))
    return rows * per_row


def grouped_bsr_call(conf: dict, tokens: int, which: str) -> tuple[float, float]:
    """(operations, bytes) of one grouped BSR call over the routed rows of
    ``tokens`` tokens: ``which`` is ``up`` (hidden x expert width, fused
    LIF) or ``down`` (rates out).  The payload counts the experts that
    can hold rows: all of them once there are as many routed rows."""
    sp = conf["spiking"]
    T, rho = sp["T"], sp["weight_density"]
    D, F = conf["hidden_size"], conf["moe_intermediate_size"]
    E, k = conf["num_experts"], conf["num_experts_per_tok"]
    K, N = (D, F) if which == "up" else (F, D)
    rows = tokens * k
    flops = 2.0 * T * rho * rows * K * N
    payload = min(E, rows) * rho * K * N * 2.0
    return flops, payload + rows * K * 4.0 + rows * N * 4.0
