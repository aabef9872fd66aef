"""Dual-sparse spiking layers built on the FTP dataflow.

Two execution paths, numerically identical in the forward pass:

* **train**: float {0,1} spikes, surrogate-gradient LIF, differentiable —
  used by BPTT training and LTH pruning (paper §V software configuration).
* **infer**: packed uint32 spike words through `ftp_layer` / the Pallas
  kernel — the LoAS execution model.

`SpikingFFN` is the first-class integration point for the LM architecture
zoo (DESIGN.md §4): a drop-in replacement for a transformer MLP block, with
the same analog-in/analog-out contract (direct encoding in, rate decoding
out), exactly the Spike-Transformer hidden-FFN workload (paper Table II,
T-HFF) the paper itself evaluates.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from .ftp import ftp_layer, ftp_spmspm, ftp_spmspm_unpacked
from .lif import (
    DEFAULT_TAU,
    DEFAULT_VTH,
    direct_encode,
    lif_forward,
    rate_decode,
)
from .packing import mask_low_activity_spikes, pack_spikes


@dataclass(frozen=True)
class SpikingConfig:
    T: int = 4
    v_th: float = DEFAULT_VTH
    tau: float = DEFAULT_TAU
    # Silent-neuron preprocessing (paper §V): mask neurons firing < 2 times.
    preprocess_min_spikes: int = 0  # 0 disables; paper uses 2
    # Fraction of weights kept after LTH pruning (paper: 1.8-3.2 % kept).
    weight_density: float = 1.0


def prune_by_magnitude(
    w: jax.Array, density: float, block: tuple[int, int] | None = None
) -> jax.Array:
    """Magnitude pruning to the target density — one LTH round's pruning
    step.  Returns the pruned weight tensor (hard zeros).

    ``block=(bk, bn)``: structured variant that keeps/drops whole (bk, bn)
    blocks ranked by L2 norm — the TPU-tile-aligned form of LTH pruning
    that the block-level inner join (kernels/join_plan.py) can actually
    skip.  Unstructured (default) pruning keeps hard zeros but rarely zeroes
    a whole MXU block.
    """
    if density >= 1.0:
        return w
    if block is None:
        k = max(1, int(w.size * density))
        topk = jax.lax.top_k(jnp.abs(w).reshape(-1), k)[0]
        thresh = jax.lax.stop_gradient(topk[k - 1])
        return jnp.where(jnp.abs(w) >= thresh, w, 0.0)
    # Two-stage: (1) keep the top ceil(nblocks * density) blocks by L2 norm
    # — concentrating the budget so the complement blocks are WHOLLY zero
    # (skippable by the join) — then (2) element-prune within the kept
    # blocks down to the exact target element count.
    bk, bn = block
    K, N = w.shape
    if K % bk or N % bn:
        raise ValueError(f"shape {(K, N)} not divisible by block {block}")
    nkb, nnb = K // bk, N // bn
    blocks = w.reshape(nkb, bk, nnb, bn)
    score = jnp.sum(
        jnp.square(blocks.astype(jnp.float32)), axis=(1, 3)
    )  # (nkb, nnb)
    nblocks = nkb * nnb
    kb = min(nblocks, max(1, -int(-nblocks * density)))
    topk = jax.lax.top_k(score.reshape(-1), kb)[0]
    thresh = jax.lax.stop_gradient(topk[kb - 1])
    keep = (score >= thresh)[:, None, :, None]
    wb = (blocks * keep.astype(w.dtype)).reshape(K, N)
    n_keep = max(1, int(w.size * density))
    if kb * bk * bn > n_keep:
        topv = jax.lax.top_k(jnp.abs(wb).reshape(-1), n_keep)[0]
        et = jax.lax.stop_gradient(topv[n_keep - 1])
        wb = jnp.where(jnp.abs(wb) >= et, wb, 0.0)
    return wb


def sparsity_mask(w: jax.Array) -> jax.Array:
    """The stored hard-zero pattern as a multiplicative {0,1} mask."""
    return (w != 0).astype(w.dtype)


def freeze_pruned(w: jax.Array) -> jax.Array:
    """Identity on the forward values, but gradients only flow to the
    SURVIVING (non-zero) entries — training can never regrow a pruned
    weight, so the prune-once density contract (and the load-time join
    plans built from it) survives fine-tuning."""
    return w * jax.lax.stop_gradient(sparsity_mask(w))


def weight_density(w) -> float:
    """Measured fraction of non-zero weights (host helper)."""
    return float(jnp.mean((jnp.asarray(w) != 0).astype(jnp.float32)))


def assert_weight_density(w, density: float, tol: float = 0.05) -> None:
    """One-shot load-time check that stored params really carry the hard
    zeros the config promises (satellite of the prune-once contract: pruning
    happens at init/load, never per forward)."""
    got = weight_density(w)
    if got > density + tol:
        raise ValueError(
            f"stored weights have density {got:.3f} > configured "
            f"{density:.3f}; prune at init/load (prune_by_magnitude) before "
            "serving the dual-sparse path"
        )


# ---------------------------------------------------------------------------
# SpikingLinear: spike-train in, spike-train out (one LoAS layer).
# ---------------------------------------------------------------------------

def spiking_linear_train(
    spikes: jax.Array, w: jax.Array, cfg: SpikingConfig
) -> jax.Array:
    """(T, M, K) float spikes x (K, N) -> (T, M, N) float spikes.

    Differentiable training path (surrogate-gradient BPTT)."""
    if cfg.preprocess_min_spikes > 0:
        spikes = mask_low_activity_spikes(spikes, cfg.preprocess_min_spikes)
    o = ftp_spmspm_unpacked(spikes, w)
    out, _ = lif_forward(o, v_th=cfg.v_th, tau=cfg.tau)
    return out


def spiking_linear_infer(
    packed: jax.Array, w: jax.Array, cfg: SpikingConfig, use_kernel: bool = False
) -> jax.Array:
    """(M, K) packed words x (K, N) -> (M, N) packed words (LoAS layer)."""
    if cfg.preprocess_min_spikes > 0:
        from .packing import mask_low_activity

        packed = mask_low_activity(packed, cfg.preprocess_min_spikes)
    if use_kernel:
        from repro.kernels import ops
        from repro.serve.policy import PACKED_DENSE

        out_packed, _ = ops.dispatch(
            packed, w, PACKED_DENSE, cfg.T,
            fuse_lif=True, v_th=cfg.v_th, tau=cfg.tau,
        )
        return out_packed
    out_packed, _ = ftp_layer(packed, w, cfg.T, v_th=cfg.v_th, tau=cfg.tau)
    return out_packed


# ---------------------------------------------------------------------------
# SpikingFFN: analog in, analog out — drop-in transformer MLP replacement.
# ---------------------------------------------------------------------------

def init_spiking_ffn(
    key,
    d_model: int,
    d_ff: int,
    dtype=jnp.float32,
    weight_density: float = 1.0,
    prune_block: tuple[int, int] | None = None,
) -> dict:
    """Init (and, when ``weight_density < 1``, LTH-prune) the FFN weights.

    Pruning happens HERE, once — the stored params carry hard zeros, and the
    apply paths below never re-prune (the prune-once/serve-many contract the
    weight join plans rely on)."""
    k1, k2 = jax.random.split(key)
    scale_in = 1.0 / (d_model ** 0.5)
    scale_out = 1.0 / (d_ff ** 0.5)
    w_in = (jax.random.normal(k1, (d_model, d_ff)) * scale_in).astype(dtype)
    w_out = (jax.random.normal(k2, (d_ff, d_model)) * scale_out).astype(dtype)
    if weight_density < 1.0:
        w_in = prune_by_magnitude(w_in, weight_density, block=prune_block)
        w_out = prune_by_magnitude(w_out, weight_density, block=prune_block)
    return {"w_in": w_in, "w_out": w_out}


def attach_join_plans(params: dict, cfg: SpikingConfig) -> dict:
    """Load-time step of the dual-sparse serving path: build one
    `WeightJoinPlan` per GEMM from the (already pruned, hard-zero) stored
    weights and return params with ``plan_in`` / ``plan_out`` attached.

    Host work happens exactly once here; afterwards every forward is
    device-only (the per-request spike join lives inside the kernel).  Also
    the single place the configured density is asserted against the stored
    weights (prune-once contract).
    """
    from repro.kernels.join_plan import build_weight_plan

    if cfg.weight_density < 1.0:
        assert_weight_density(params["w_in"], cfg.weight_density)
        assert_weight_density(params["w_out"], cfg.weight_density)
    import numpy as np

    return dict(
        params,
        plan_in=build_weight_plan(np.asarray(params["w_in"])),
        plan_out=build_weight_plan(np.asarray(params["w_out"])),
    )


def spiking_ffn_apply_packed(
    params: dict,
    packed_in: jax.Array,
    cfg: SpikingConfig,
    plans: tuple | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Spike-domain FFN: packed words in, (analog out, packed hidden words).

    ``packed_in``: (..., d_model) uint32 — one spike word per neuron, bit t
    = timestep t.  Callers that already hold activations as packed words
    (the serving engine's spike cache, spike-stream pipelines) skip the
    direct-encode step and keep the hidden activations packed for reuse —
    nothing is unpacked to (T, ...) float32 between layers.

    Weights must already carry their hard zeros (pruned at init/load — this
    function never prunes).  When join plans are available (``plans`` arg or
    ``plan_in``/``plan_out`` attached by `attach_join_plans`), both GEMMs run
    dual-sparse through the BSR kernel: static weight join from the plan,
    per-request spike join on device.
    """
    w_in, w_out = params["w_in"], params["w_out"]
    if plans is None:
        plans = (params.get("plan_in"), params.get("plan_out"))
    plan_in, plan_out = plans
    lead = packed_in.shape[:-1]
    pm = packed_in.reshape(-1, packed_in.shape[-1])
    if cfg.preprocess_min_spikes > 0:
        from .packing import mask_low_activity

        with jax.named_scope("ffn.encode"):
            pm = mask_low_activity(pm, cfg.preprocess_min_spikes)
    if plan_in is not None:
        packed_h, o = _ffn_dual_sparse(pm, plan_in, plan_out, w_in, w_out, cfg)
    else:
        packed_h = _ffn_up_lif(pm, w_in, cfg)
        with jax.named_scope("ffn.down"):
            o = ftp_spmspm(packed_h, w_out, cfg.T)
    y = rate_decode(o)
    return (
        y.reshape(*lead, -1),
        packed_h.reshape(*lead, -1),
    )


def _ffn_up_lif(pm, w_in, cfg: SpikingConfig):
    """The jnp hidden layer: up GEMM (``ffn.up``), then the P-LIF epilogue
    (``ffn.lif``); packed hidden words out."""
    with jax.named_scope("ffn.up"):
        o = ftp_spmspm(pm, w_in, cfg.T)
    with jax.named_scope("ffn.lif"):
        spikes, _ = lif_forward(o, v_th=cfg.v_th, tau=cfg.tau, unroll=True)
        return pack_spikes(spikes)


def _ffn_dual_sparse(pm, plan_in, plan_out, w_in, w_out, cfg: SpikingConfig):
    """Both FFN GEMMs through the dual-sparse BSR kernel: fused P-LIF on the
    hidden layer (packed words out), plain full sums on the output layer.
    Returns (packed hidden words (M, F), full sums (T, M, D)).  The LIF runs
    inside the ``ffn.up`` kernel, so no operation carries ``ffn.lif``."""
    from repro.kernels import ops
    from repro.serve.policy import PACKED_DUAL

    with jax.named_scope("ffn.up"):
        packed_h, _ = ops.dispatch(
            pm, plan_in, PACKED_DUAL, cfg.T,
            fuse_lif=True, v_th=cfg.v_th, tau=cfg.tau,
            n_out=w_in.shape[1],
        )
    with jax.named_scope("ffn.down"):
        o, _ = ops.dispatch(
            packed_h, plan_out, PACKED_DUAL, cfg.T,
            fuse_lif=False, n_out=w_out.shape[1],
        )
    return packed_h, o


def spiking_ffn_apply(
    params: dict,
    x: jax.Array,
    cfg: SpikingConfig,
    mode: str = "train",
    use_kernel: bool = False,
    plans: tuple | None = None,
) -> jax.Array:
    """x: (..., d_model) analog activations -> (..., d_model).

    Pipeline: direct-encode(x) -> spikes --W_in--> LIF -> spikes --W_out-->
    potentials -> rate decode.  Both GEMMs are dual-sparse spMspM under the
    FTP dataflow; weights carry their LTH-pruned hard zeros from init/load
    (this function never prunes — prune-once contract).

    ``plans``: optional (plan_in, plan_out) `WeightJoinPlan` pair (or attach
    them to ``params`` via `attach_join_plans`); in ``infer`` mode they route
    both GEMMs through the dual-sparse BSR kernel.
    """
    w_in, w_out = params["w_in"], params["w_out"]
    if plans is None:
        plans = (params.get("plan_in"), params.get("plan_out"))
    plan_in, plan_out = plans

    lead = x.shape[:-1]
    d_model = x.shape[-1]
    xm = x.reshape(-1, d_model)  # (M, K)
    with jax.named_scope("ffn.encode"):
        spikes_in = direct_encode(xm, cfg.T, v_th=cfg.v_th, tau=cfg.tau)

    if mode == "train":
        if cfg.weight_density < 1.0:
            # freeze the stored LTH pattern: gradients reach surviving
            # weights only, so BPTT fine-tuning never regrows a pruned zero
            w_in, w_out = freeze_pruned(w_in), freeze_pruned(w_out)
        hidden = spiking_linear_train(spikes_in, w_in, cfg)  # (T, M, F)
        o = ftp_spmspm_unpacked(hidden, w_out)               # (T, M, D)
        y = rate_decode(o)
    elif mode == "infer":
        with jax.named_scope("ffn.encode"):
            packed_in = pack_spikes(spikes_in)
            if cfg.preprocess_min_spikes > 0:
                from .packing import mask_low_activity

                packed_in = mask_low_activity(
                    packed_in, cfg.preprocess_min_spikes
                )
        if plan_in is not None:
            _, o = _ffn_dual_sparse(
                packed_in, plan_in, plan_out, w_in, w_out, cfg
            )
        elif use_kernel:
            from repro.kernels import ops
            from repro.serve.policy import PACKED_DENSE

            with jax.named_scope("ffn.up"):
                packed_h, _ = ops.dispatch(
                    packed_in, w_in, PACKED_DENSE, cfg.T,
                    fuse_lif=True, v_th=cfg.v_th, tau=cfg.tau,
                )
            with jax.named_scope("ffn.down"):
                o = ops.dispatch(packed_h, w_out, PACKED_DENSE, cfg.T)
        else:
            packed_h = _ffn_up_lif(packed_in, w_in, cfg)
            with jax.named_scope("ffn.down"):
                o = ftp_spmspm(packed_h, w_out, cfg.T)
        y = rate_decode(o)
    else:
        raise ValueError(f"unknown mode {mode!r}")
    return y.reshape(*lead, -1).astype(x.dtype)
