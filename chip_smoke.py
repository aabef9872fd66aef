#!/usr/bin/env python3
"""Smoke run of the dual-sparse serving path on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # sharded serving, 2x2 (data, model) mesh

Serves llama3.2-1B at its published widths (16 layers, d_model 2048, d_ff
8192, vocab 128256; f32 params drawn from ``--seed``) with its MLPs swapped
for spiking FFNs pruned to weight density 0.3.  `ExecutionPolicy.for_arch`
then picks packed spikes and dual-sparse weights: the engine builds the
block join plans at load and every FFN GEMM runs the Pallas BSR kernel,
compiled for the chip.  Four prompts of 128 tokens each generate 16 tokens
through `Engine.generate_batch`, as `repro.launch.serve` builds it.

One chip checks that
  * JAX's first device is a TPU (before any other work: JAX falls back to
    the CPU when the TPU does not come up);
  * the compiled decode step holds the kernel (``tpu_custom_call``), so it
    did not run in the Pallas interpreter;
  * the engine's tokens equal the reference loop's (`launch.serve.generate`
    on the same params and execution mode), and its logits of all 16
    tokens equal, bit for bit, those of the compiled step programs driven
    as a greedy loop;
  * the prefill logits of the packed kernel path and the jnp float path
    pick the same token for every prompt, and differ by less than half
    the smallest top-1 minus top-2 logit margin;
  * layer 0's dual-sparse full sums equal the jnp float path on the same
    {0,1} spikes and bf16 weights, to f32 accumulation-order rounding;
  * the logits are finite.
``--chips 4`` runs only the sharded phase: one process drives four chips
as a data=2 x model=2 serve mesh, checks that every join-plan slab sits on
its own model column, that the mesh engine's tokens equal a one-chip
engine's on the same prompts, and that its logits moved by less than half
the one-chip engine's smallest top-1 minus top-2 margin.

Each phase runs in this one process (a chip belongs to one process).  Any
failed check ends the run with a non-zero exit and no result line; the
last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

ARCH = "llama3_2_1b"
WEIGHT_DENSITY = 0.3
N_PROMPTS, PROMPT_LEN, GEN = 4, 128, 16
DRIFT_ROWS, DRIFT_SPIKE_DENSITY = 512, 0.2


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def require_tpu(n_chips: int):
    """The first device, after checking that JAX found ``n_chips`` TPUs."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU — JAX's first device is {dev.platform!r} "
            f"({dev.device_kind}); this smoke runs only on a TPU"
        )
    if len(devices) < n_chips:
        raise SystemExit(
            f"chip_smoke: {n_chips} TPU chips needed, JAX sees {len(devices)}"
        )
    return dev


def smoke_config(cfg=None):
    """The served configuration: the published llama3.2-1B widths with
    spiking FFNs at weight density 0.3 (pruned once, at init)."""
    from repro.configs import get_config

    cfg = get_config(ARCH) if cfg is None else cfg
    return dataclasses.replace(
        cfg, spiking_ffn=True, spiking_weight_density=WEIGHT_DENSITY
    )


def describe_config(cfg) -> str:
    return (f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
            f"d_ff {cfg.d_ff}, vocab {cfg.vocab}, heads {cfg.n_heads} "
            f"(kv {cfg.n_kv}), T {cfg.spiking_T}, weight density "
            f"{cfg.spiking_weight_density}, params {cfg.param_dtype}, "
            f"compute {cfg.compute_dtype}")


def build(cfg, seed: int, n_prompts: int, prompt_len: int):
    """Model, seeded random params and prompts, as `launch.serve` makes
    them."""
    import jax

    from repro.models.registry import build_model

    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    prompts = [
        np.asarray(rng.integers(0, cfg.vocab, size=(prompt_len,)), np.int32)
        for _ in range(n_prompts)
    ]
    return model, params, prompts


@contextlib.contextmanager
def packed_inference():
    """Trace spiking FFNs in packed-inference mode, as the engine does."""
    from repro.models import layers

    prev = layers.get_spiking_ffn_mode()
    layers.set_spiking_ffn_mode("infer")
    try:
        yield
    finally:
        layers.set_spiking_ffn_mode(prev)


def full_sum_drift(params, cfg, seed: int, rows: int = DRIFT_ROWS):
    """Layer 0's output GEMM (d_ff -> d_model) on random {0,1} spikes:
    the dual-sparse kernel's unfused full sums against the jnp float path
    with the same bf16 weights.  Both add the same exact products in f32,
    in different orders, so they may differ by at most (K-1) * 2^-24 *
    sum|a*w| (the summation error bound).  Returns (max |diff|, tol)."""
    import jax
    import jax.numpy as jnp

    from repro.core.ftp import ftp_spmspm_unpacked
    from repro.core.packing import pack_spikes
    from repro.kernels import ops
    from repro.serve.policy import PACKED_DUAL

    mlp = params["layers"]["mlp"]
    plan = jax.tree.map(lambda x: x[0], mlp["plan_out"])
    w = mlp["wd"][0].astype(cfg.compute_dtype)
    K, N = w.shape
    T = cfg.spiking_T
    rng = np.random.default_rng(seed)
    spikes = jnp.asarray(rng.random((T, rows, K)) < DRIFT_SPIKE_DENSITY,
                         jnp.bfloat16)
    got, _ = ops.dispatch(pack_spikes(spikes), plan, PACKED_DUAL, T,
                          fuse_lif=False, n_out=N)
    want = ftp_spmspm_unpacked(spikes, w)
    scale = float(jnp.max(ftp_spmspm_unpacked(spikes, jnp.abs(w))))
    tol = (K - 1) * 2.0 ** -24 * scale
    return float(jnp.max(jnp.abs(got - want))), tol


def greedy_step_logits(prefill, decode, params, tokens, cache, steps: int):
    """Greedy generation through the step programs, keeping every step's
    last-position logits: ((B, steps, vocab) float32, seconds of the first
    prefill and first decode call).  ``cache`` is consumed."""
    import jax
    import jax.numpy as jnp

    seconds = []
    rows = []
    for i in range(steps):
        t = time.perf_counter()
        if i == 0:
            logits, cache = prefill(params, {"tokens": tokens}, cache)
        else:
            logits, cache = decode(
                params, jnp.argmax(rows[-1], axis=-1)[:, None], cache)
        if i < 2:
            jax.block_until_ready(logits)
            seconds.append(time.perf_counter() - t)
        rows.append(logits[:, -1])
    return np.stack([np.asarray(r, np.float32) for r in rows], axis=1), seconds


def one_chip(seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.launch.serve import generate
    from repro.serve import Engine, ExecutionPolicy

    cfg = smoke_config()
    print(f"config: {describe_config(cfg)}")
    policy = ExecutionPolicy.for_arch(cfg)
    print(f"policy: {policy.describe()}")
    check(policy.spike_format == "packed"
          and policy.weight_sparsity == "dual_sparse",
          "for_arch did not pick the packed dual-sparse policy")

    t = time.perf_counter()
    model, params, prompts = build(cfg, seed, N_PROMPTS, PROMPT_LEN)
    jax.block_until_ready(params)
    print(f"init: {time.perf_counter() - t:.2f}s")
    max_len = PROMPT_LEN + GEN
    t = time.perf_counter()
    engine = Engine(model, params, max_len=max_len, max_slots=N_PROMPTS,
                    policy=policy, capture_logits=True)
    jax.block_until_ready(engine.params)
    print(f"engine load (join plans): {time.perf_counter() - t:.2f}s")

    # The engine's two step programs, compiled ahead to read the decode
    # step's HLO, then driven as a greedy loop that keeps every step's
    # logits: the reference for the engine's logit traces.
    tokens = jnp.asarray(np.stack(prompts))
    cache = model.init_cache(N_PROMPTS, max_len)
    with packed_inference():
        t = time.perf_counter()
        prefill = jax.jit(model.prefill, donate_argnums=(2,)).lower(
            engine.params, {"tokens": tokens}, cache).compile()
        t_prefill = time.perf_counter() - t
        t = time.perf_counter()
        decode = jax.jit(model.decode, donate_argnums=(2,)).lower(
            engine.params, tokens[:, :1], cache).compile()
        t_decode = time.perf_counter() - t
    print(f"compile: prefill {t_prefill:.2f}s, decode {t_decode:.2f}s")
    n_kernels = decode.as_text().count("tpu_custom_call")
    print(f"compiled decode step: {n_kernels} tpu_custom_call")
    check(n_kernels > 0, "the compiled decode step holds no "
          "tpu_custom_call: the BSR kernel was not compiled for the chip")

    step_logits, t_first = greedy_step_logits(
        prefill, decode, engine.params, tokens, cache, GEN)
    print(f"first call: prefill {t_first[0]:.3f}s, decode {t_first[1]:.3f}s")
    finite = bool(np.isfinite(step_logits).all())
    print(f"step-program logits {step_logits.shape} finite: {finite}")
    check(finite, "non-finite logits")

    t = time.perf_counter()
    outs = engine.generate_batch(prompts, GEN)
    print(f"engine.generate_batch: {len(outs)} requests x {GEN} tokens in "
          f"{time.perf_counter() - t:.2f}s (its own compiles included)")
    print_samples(outs)
    with packed_inference():
        ref = np.asarray(generate(model, engine.params, tokens,
                                  model.init_cache(N_PROMPTS, max_len), GEN))
    same = all(np.array_equal(ref[i], outs[i]) for i in range(N_PROMPTS))
    print(f"engine tokens == reference loop (same params, packed "
          f"dual-sparse mode): {same}")
    check(same, "engine tokens differ from the reference loop's")
    check(np.array_equal(step_logits.argmax(-1), ref),
          "the step-program loop's tokens differ from the reference loop's")
    # Random weights may repeat one token, which leaves token identity
    # little to see: every decode step's logits must match bit for bit.
    traces = engine.drain_logit_traces()
    check(all(len(tr) == GEN for tr in traces),
          f"engine logit traces of {[len(tr) for tr in traces]} tokens, "
          f"not {GEN}")
    engine_logits = np.stack([np.stack(tr) for tr in traces])
    print(f"engine vs step-program logits of all {GEN} tokens: max |diff| "
          f"{float(np.abs(engine_logits - step_logits).max())!r}")
    check(np.array_equal(engine_logits, step_logits),
          "the engine's logits differ from the step programs' (bitwise "
          "policy)")

    # The fused-LIF input GEMM reaches the logits only through its spikes:
    # the jnp float path on the same weights must pick the same tokens, by
    # a difference too small to flip any prompt's greedy choice.
    float_logits, _ = jax.jit(model.prefill)(
        params, {"tokens": tokens}, model.init_cache(N_PROMPTS, max_len))
    float_last = np.asarray(float_logits[:, -1], np.float32)
    kernel_last = step_logits[:, 0]
    diff = float(np.abs(float_last - kernel_last).max())
    bound = top2_margin(kernel_last) / 2
    same_argmax = int((float_last.argmax(-1) == kernel_last.argmax(-1)).sum())
    print(f"prefill logits, jnp float path vs packed kernel path: max |diff| "
          f"{diff!r}, bound {bound!r} = half the smallest top-1 minus top-2 "
          f"logit margin; argmax equal for {same_argmax}/{N_PROMPTS} prompts")
    check(same_argmax == N_PROMPTS and diff <= bound,
          "the packed kernel path's prefill logits are off the float path's")

    drift, tol = full_sum_drift(engine.params, cfg, seed)
    print(f"layer-0 full sums, dual-sparse kernel vs jnp float path "
          f"({DRIFT_ROWS} rows, d_ff -> d_model): max |diff| {drift!r}, "
          f"tolerance {tol!r} = (K-1) * 2^-24 * max sum|a*w|")
    check(drift <= tol, f"full-sum drift {drift} exceeds {tol}")


def four_chips(seed: int) -> None:
    import jax

    from repro.serve import Engine, ExecutionPolicy, Placement
    from repro.serve.policy import max_logit_drift
    from repro.serve.sharding import make_serve_mesh

    mesh = make_serve_mesh("data=2,model=2", devices=jax.devices()[:4])
    check(mesh is not None,
          "make_serve_mesh gave no mesh: serving would run unsharded")
    ids = [d.id for d in mesh.devices.flat]
    print(f"mesh: {dict(mesh.shape)} over device ids "
          f"{[[d.id for d in row] for row in mesh.devices]}")
    check(len(set(ids)) == 4, f"mesh repeats devices: {ids}")
    cfg = smoke_config()
    print(f"config: {describe_config(cfg)}")
    policy = ExecutionPolicy.for_arch(cfg, placement=Placement(mesh=mesh))
    print(f"policy: {policy.describe()}")
    model, params, prompts = build(cfg, seed, N_PROMPTS, PROMPT_LEN)
    max_len = PROMPT_LEN + GEN

    t = time.perf_counter()
    engine = Engine(model, params, max_len=max_len, max_slots=N_PROMPTS,
                    policy=policy, capture_logits=True)
    jax.block_until_ready(engine.params)
    print(f"mesh engine load (sharded join plans): "
          f"{time.perf_counter() - t:.2f}s")
    check_plan_placement(engine.params, mesh)
    t = time.perf_counter()
    outs = engine.generate_batch(prompts, GEN)
    print(f"mesh engine.generate_batch: {time.perf_counter() - t:.2f}s "
          "(compiles included)")
    summary = engine.summary()
    print(f"mesh summary: mesh {summary['mesh']}, "
          f"mesh_devices {summary['mesh_devices']}")
    check(summary["mesh_devices"] == 4,
          f"mesh_devices {summary['mesh_devices']} != 4")
    mesh_logits = engine.drain_logit_traces()
    del engine

    single = Engine(model, params, max_len=max_len, max_slots=N_PROMPTS,
                    policy=ExecutionPolicy.for_arch(cfg), capture_logits=True)
    ref = single.generate_batch(prompts, GEN)
    print_samples(outs)
    same = all(np.array_equal(a, b) for a, b in zip(ref, outs))
    print(f"mesh tokens == one-chip engine tokens: {same}")
    check(same, "sharded serving tokens differ from the one-chip engine's")
    # The logits of the emitted tokens may move (not bitwise on the chip),
    # but by less than could flip any token: identity is not luck.
    ref_logits = single.drain_logit_traces()
    drift = max(max_logit_drift(r, o, rl, ol) for r, o, rl, ol in zip(
        ref, outs, ref_logits, mesh_logits))
    bound = top2_margin(np.concatenate([np.stack(tr) for tr in ref_logits])) / 2
    print(f"mesh vs one-chip logits of every emitted token: max |diff| "
          f"{drift!r}, bound {bound!r} = half the smallest top-1 minus "
          f"top-2 logit margin")
    check(drift <= bound, f"mesh logit drift {drift} exceeds {bound}")


def top2_margin(logits) -> float:
    """The smallest gap between the largest and second-largest logit of
    any row of ``logits`` (..., vocab)."""
    top2 = np.partition(logits, -2, axis=-1)[..., -2:]
    return float((top2[..., 1] - top2[..., 0]).min())


def print_samples(outs) -> None:
    for i, o in enumerate(outs):
        print(f"sample[{i}]: {o.tolist()}")
    # random weights may settle into repeating one token; few distinct
    # tokens make token identity a weak check
    print(f"distinct tokens per request: {[len(set(o.tolist())) for o in outs]}")


def check_plan_placement(params, mesh) -> None:
    """Every join-plan slab lives on the devices of its own model column
    (and nowhere else), for both FFN GEMMs."""
    column = {d.id: m for (_, m), d in np.ndenumerate(mesh.devices)}
    for name in ("plan_in", "plan_out"):
        payload = params["layers"]["mlp"][name].payload  # (L, shards, ...)
        shards = payload.addressable_shards
        devs = {s.device.id for s in shards}
        check(devs == set(column),
              f"{name} payload on devices {sorted(devs)}, mesh has "
              f"{sorted(column)}")
        for s in shards:
            m = column[s.device.id]
            check(s.index[1] == slice(m, m + 1),
                  f"{name} slab {s.index[1]} on device {s.device.id} of "
                  f"model column {m}")
    print(f"plan slabs: each on its own model column of "
          f"{len(column)} devices")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip checks; 4: only the sharded "
                         "serving phase on a data=2 x model=2 mesh")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random params, prompts and spikes")
    args = ap.parse_args(argv)

    import jax

    dev = require_tpu(args.chips)  # first: JAX falls back to the CPU
    print(f"device: {dev.platform} {dev.device_kind}")
    root = Path(__file__).resolve().parent
    sys.path.insert(0, str(root / "src"))
    from repro.launch.compile_cache import configure_compile_cache

    print(f"compile cache: {configure_compile_cache(root)}")
    if args.chips == 4:
        four_chips(args.seed)
    else:
        one_chip(args.seed)
    for d in jax.devices()[:args.chips]:
        peak = (d.memory_stats() or {}).get("peak_bytes_in_use")
        print(f"peak_bytes_in_use (device {d.id}): {peak}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
