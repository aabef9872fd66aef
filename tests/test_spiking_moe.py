"""Spiking dual-sparse experts and mixed sliding/full attention, at a small
size on the CPU, against the benchmark's plain float32 reference
(`bench/reference/spiking_moe_decoder.py`) on seeded weights from its
adapter: hidden 256, 8 experts top-2 of width 128, 4 layers (3 sliding
with window 16 : 1 full with YaRN), vocab 512.

* Prefill and then decode through the engine's packed dual-sparse path
  (grouped BSR kernel in the Pallas interpreter) give the reference's
  logits: at float32 compute and a float32 KV cache within 1e-4.  The
  two differ only in the order float32 sums are taken (readings ~3e-6);
  a single spike that fires on one side only moves logits by 1e-2 or
  more, so the bound admits rounding and no flipped spike.
* A request's tokens are the same bits solo, in a batch of four, in
  cohorts that merge, and under speculation: routing drops nothing, so
  rows stay independent.
* A router that sends every token to the same experts drops nothing.
* The grouped BSR call equals a loop of the dense BSR call over the
  experts bit for bit, experts with no rows included, and a one-token
  decode fetches the payload of its own experts only.
* The YaRN table is the published formula's, and a sliding layer does not
  see position ``i - window``.
"""
import dataclasses
import importlib.util
import json
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ftp_spmm, ops
from repro.kernels.join_plan import build_weight_plan, stack_plans
from repro.models import layers, transformer
from repro.models.registry import build_model
from repro.serve import Engine, ExecutionPolicy
from repro.serve.policy import FLOAT_DENSE, PACKED_DUAL, draft

BENCH = Path(__file__).resolve().parents[1] / "bench"
MAX_LEN = 64


def _load(kind: str):
    name = f"{kind}_spiking_moe_decoder"
    if name not in sys.modules:
        s = importlib.util.spec_from_file_location(
            name, BENCH / kind / "spiking_moe_decoder.py")
        mod = importlib.util.module_from_spec(s)
        sys.modules[name] = mod
        s.loader.exec_module(mod)
    return sys.modules[name]


def _conf(compute="float32"):
    conf = json.loads(
        (BENCH / "configs" / "mellum2-12b-a2.5b-spk.json").read_text())
    conf.update(hidden_size=256, num_experts=8, num_experts_per_tok=2,
                moe_intermediate_size=128, num_attention_heads=4,
                num_key_value_heads=2, head_dim=64, vocab_size=512,
                sliding_window=16)
    conf["spiking"]["weight_density"] = 0.5
    conf["dtypes"]["compute"] = compute
    conf["rope_parameters"]["full_attention"].update(
        original_max_position_embeddings=32, factor=4)
    return conf


_CACHE = {}


def _setup(seed=1, compute="float32"):
    """(conf, cfg, model with a float32 KV cache, weights)."""
    key = (seed, compute)
    if key not in _CACHE:
        conf = _conf(compute)
        ad = _load("adapters")
        cfg = ad.arch_config(conf)
        model = build_model(cfg)
        model = dataclasses.replace(model, init_cache=lambda b, s: (
            transformer.init_cache(cfg, b, s, dtype=jnp.float32)))
        _CACHE[key] = (conf, cfg, model, ad.make_weights(conf, seed))
    return _CACHE[key]


def _prompts(lens, seed):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 512, size=n, dtype=np.int32) for n in lens]


def _engine(model, w, cfg, **kw):
    return Engine(model, w, max_len=MAX_LEN, max_slots=4,
                  policy=ExecutionPolicy.for_arch(cfg), **kw)


def test_the_model_is_served_packed_dual_sparse_with_independent_rows():
    conf, cfg, model, w = _setup(3, compute="bfloat16")
    pol = ExecutionPolicy.for_arch(cfg)
    assert (pol.spike_format, pol.weight_sparsity) == ("packed", "dual_sparse")
    engine = _engine(model, w, cfg)
    assert engine.row_independent and engine.merge_cohorts
    moe = engine.params["layers"]["moe"]
    # one plan per (layer, expert), payload in the compute dtype; the
    # spiking experts' masters are left for the plans, not cast
    assert moe["plan_in"].payload.shape[:2] == (4, 8)
    assert moe["plan_out"].kidx.shape[:2] == (4, 8)
    assert moe["plan_in"].payload.dtype == jnp.bfloat16
    assert moe["wu"].dtype == moe["router"].dtype == jnp.float32
    assert engine.params["layers"]["attn"]["wq"].dtype == jnp.bfloat16
    capacity = dataclasses.replace(cfg, spiking_ffn=False)
    assert layers.rows_independent(cfg)
    assert not layers.rows_independent(capacity)


def test_speculation_follows_the_routing():
    _, cfg, _, _ = _setup()
    ExecutionPolicy.for_arch(cfg, speculation=draft(FLOAT_DENSE, k=2))
    capacity = dataclasses.replace(cfg, spiking_ffn=False,
                                   spiking_weight_density=1.0)
    with pytest.raises(ValueError, match="with a capacity"):
        ExecutionPolicy.for_arch(capacity,
                                 speculation=draft(FLOAT_DENSE, k=2))


@pytest.mark.parametrize("sparser", [False, True], ids=["float", "pruned"])
def test_speculative_decode_is_the_plain_decode(sparser):
    """A float draft (every expert on every token) or a further-pruned
    packed draft (its own per-expert plans) proposes; the target's tokens
    come out unchanged."""
    _, cfg, model, w = _setup(3, compute="bfloat16")
    target = ExecutionPolicy.for_arch(cfg)
    spec = (draft(target, k=2, draft_weight_density=0.25) if sparser
            else draft(FLOAT_DENSE, k=2))
    prompts = _prompts([12, 20], 6)
    plain = _engine(model, w, cfg).generate_batch(prompts, 8)
    engine = Engine(model, w, max_len=MAX_LEN, max_slots=4,
                    policy=ExecutionPolicy.for_arch(cfg, speculation=spec))
    for a, b in zip(plain, engine.generate_batch(prompts, 8)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("seed", [1, 2])
def test_engine_logits_are_the_reference(seed):
    conf, cfg, model, w = _setup(seed)
    ref = _load("reference")
    engine = _engine(model, w, cfg, capture_logits=True)
    prompts = _prompts([40, 24, 9, 33], seed)
    reqs = [engine.submit(p, 12) for p in prompts]
    engine.run()
    traces = engine.drain_logit_traces()
    for r, p in zip(reqs, prompts):
        toks = np.asarray(engine.results[r.rid].generated)
        want, rates = ref.served_logits(conf, w, p, toks, MAX_LEN, 16)
        got = np.stack([np.asarray(x).reshape(-1) for x in traces[r.rid]])
        assert got.shape == want.shape
        assert np.abs(got - want).max() < 1e-4
        assert 0.1 < rates[0] < 0.4 and 0.0 < rates[1] < 0.2


def _solo(model, w, cfg, prompts, gens):
    out = []
    for p, g in zip(prompts, gens):
        out.append(_engine(model, w, cfg).generate_batch([p], g)[0])
    return out


def test_tokens_are_the_same_bits_solo_batched_and_merged():
    _, cfg, model, w = _setup(3, compute="bfloat16")
    lens, gens = [20, 20, 20, 20], [6, 6, 6, 6]
    prompts = _prompts(lens, 3)
    solo = _solo(model, w, cfg, prompts, gens)
    batched = _engine(model, w, cfg).generate_batch(prompts, 6)
    for a, b in zip(solo, batched):
        np.testing.assert_array_equal(a, b)
    # staggered arrivals: later prefills join cohorts already decoding
    lens, gens, arrivals = [8, 8, 12, 8, 12, 16], [6, 8, 5, 6, 5, 6], \
        [0, 0, 0, 4, 4, 8]
    prompts = _prompts(lens, 4)
    solo = _solo(model, w, cfg, prompts, gens)
    engine = Engine(model, w, max_len=MAX_LEN, max_slots=4, batch_align=2,
                    policy=ExecutionPolicy.for_arch(cfg))
    reqs, i, step = [], 0, 0
    while not (engine.idle and i == len(prompts)):
        while i < len(prompts) and arrivals[i] <= step:
            reqs.append(engine.submit(prompts[i], gens[i]))
            i += 1
        engine.step()
        step += 1
    s = engine.summary()
    assert s["cohort_merges"] >= 1 and s["padded_rows"] >= 1
    for r, want in zip(reqs, solo):
        np.testing.assert_array_equal(
            want, np.asarray(engine.results[r.rid].generated, np.int32))


def test_every_token_routed_to_the_same_experts_drops_nothing():
    """A zero router ties every logit; top-k then picks experts 0 and 1
    for every token, far past what capacity 1.25 would have kept."""
    conf, cfg, model, w = _setup(5)
    w = jax.tree.map(lambda a: a, w)
    w["layers"]["moe"]["router"] = jnp.zeros_like(w["layers"]["moe"]["router"])
    ref = _load("reference")
    engine = _engine(model, w, cfg, capture_logits=True)
    (p,) = _prompts([48], 5)
    r = engine.submit(p, 8)
    engine.run()
    toks = np.asarray(engine.results[r.rid].generated)
    got = np.stack([np.asarray(x).reshape(-1)
                    for x in engine.drain_logit_traces()[r.rid]])
    want, _ = ref.served_logits(conf, w, p, toks, MAX_LEN, 16)
    assert np.abs(got - want).max() < 1e-4
    dropped, _ = ref.served_logits(conf, w, p, toks, MAX_LEN, 16,
                                   variant="capacity_1.25")
    assert np.abs(dropped - want).max() > 1e-2


def _expert_plans(rng, E, K, N, density=0.5):
    ws = []
    for _ in range(E):
        keep = rng.random((K // 128, N // 128)) < density
        keep[0, 0] = True
        mask = np.kron(keep, np.ones((128, 128)))
        ws.append((rng.standard_normal((K, N)) / np.sqrt(K) * mask)
                  .astype(np.float32))
    return ws, stack_plans([build_weight_plan(x) for x in ws])


@pytest.mark.parametrize("fuse_lif", [True, False], ids=["up_lif", "down_rate"])
def test_grouped_call_is_a_loop_of_dense_calls(fuse_lif):
    rng = np.random.default_rng(0)
    E, K, N, T, k = 6, 256, 384, 4, 2
    ws, plan = _expert_plans(rng, E, K, N)
    # 13 tokens routed to experts 0-3 only: 4 and 5 hold no rows
    eidx = jnp.asarray(rng.choice([0, 2, 3], size=(13, k)))
    eidx = eidx.at[:, 1].set((eidx[:, 0] + 2) % 4)
    bm = 8
    src, dest, groups = ops.group_rows(eidx, E, bm)
    words = jnp.asarray(rng.integers(0, 16, size=(13, K), dtype=np.uint32)
                        * (rng.random((13, K)) < 0.3))
    a = jnp.concatenate([words, jnp.zeros((1, K), jnp.uint32)])[src]
    got = ops.dispatch(a, plan, PACKED_DUAL, T, fuse_lif=fuse_lif, n_out=N,
                       groups=groups)
    for e in range(E):
        rows = np.asarray(dest)[np.asarray(eidx) == e]
        tok = np.nonzero(np.asarray(eidx) == e)[0]
        if not rows.size:
            continue
        one = build_weight_plan(ws[e])
        out, _ = ops.dispatch(words[tok], one, PACKED_DUAL, T,
                              fuse_lif=fuse_lif, n_out=N)
        if not fuse_lif:
            out = ftp_spmm.rate_epilogue(out.reshape(T * len(tok), N), T)
        np.testing.assert_array_equal(np.asarray(got)[rows], np.asarray(out))
    # every row of every tile the call ran belongs to one expert
    n_live = int(groups[-1])
    tile_expert = np.asarray(groups[:n_live])
    for e, r in zip(np.asarray(eidx).ravel(), np.asarray(dest).ravel()):
        assert tile_expert[r // bm] == e


def test_a_one_token_decode_fetches_only_its_experts():
    """Replays the grouped kernel's block index maps over its grid and
    keeps each step whose payload block differs from the previous step's:
    the blocks a pipelined kernel fetches."""
    rng = np.random.default_rng(1)
    E, K, N, k = 16, 512, 256, 4
    _, plan = _expert_plans(rng, E, K, N)
    eidx = jnp.asarray([[3, 9, 12, 5]])
    bm = ops.grouped_row_block(eidx.size, E)
    _, _, groups = ops.group_rows(eidx, E, bm)
    nm = ops.grouped_tiles(eidx.size, E, bm)
    cnt, vidx = np.asarray(plan.cnt).ravel(), np.asarray(plan.vidx).ravel()
    geo = dict(nm=nm, nnb=plan.nnb, jmax=plan.jmax)
    fetched, prev = set(), None
    for i in range(nm):
        for j in range(plan.nnb):
            for jj in range(plan.jmax):
                _, _, e, slot = ftp_spmm.grouped_slot(
                    i, j, jj, cnt, np.asarray(groups), **geo)
                blk = (int(e), int(vidx[slot]))
                if blk != prev:
                    fetched.add(blk)
                prev = blk
    assert {e for e, _ in fetched} == {3, 5, 9, 12}
    want = {(e, int(v)) for e in (3, 5, 9, 12)
            for v in np.unique(np.asarray(plan.vidx)[e][
                np.arange(plan.jmax)[None] < np.asarray(plan.cnt)[e][:, None]])}
    assert fetched == want


def test_yarn_table_is_the_published_formula():
    dh, theta, factor, orig, fast, slow = 128, 500000.0, 16.0, 8192, 32, 1
    inv, af = layers.yarn_inv_freq(dh, theta, factor, orig, fast, slow)
    # YaRN (Peng et al. 2023), the Hugging Face form: correction range in
    # dimensions, floored / ceiled, blend of extrapolated and
    # interpolated frequencies by a linear ramp over that range
    low = math.floor(dh * math.log(orig / (fast * 2 * math.pi))
                     / (2 * math.log(theta)))
    high = math.ceil(dh * math.log(orig / (slow * 2 * math.pi))
                     / (2 * math.log(theta)))
    want = []
    for i in range(dh // 2):
        f = theta ** (-2.0 * i / dh)
        ramp = min(max((i - max(low, 0)) / (min(high, dh - 1) - max(low, 0)),
                       0.0), 1.0)
        want.append(f / factor * ramp + f * (1 - ramp))
    np.testing.assert_allclose(inv, np.asarray(want), rtol=2e-6)
    assert af == pytest.approx(1.2772588722239782)
    ref = _load("reference")
    np.testing.assert_array_equal(
        ref._yarn(dh, theta, factor, orig, fast, slow), inv)


@pytest.mark.parametrize("sliding", [True, False])
def test_a_sliding_layer_does_not_see_position_i_minus_window(sliding):
    _, cfg, _, _ = _setup()
    W = cfg.window
    rng = np.random.default_rng(2)
    S = 2 * W + 4
    q = jnp.asarray(rng.standard_normal((1, S, 4, 64)), jnp.float32)
    kv = [jnp.asarray(rng.standard_normal((1, S, 2, 64)), jnp.float32)
          for _ in range(2)]
    i = S - 1

    def attend(k, v):
        return layers.multihead_attention(q, k, v, cfg,
                                          sliding=jnp.asarray(sliding))[0, i]

    base = attend(*kv)
    far = [x.at[0, i - W].set(100.0) for x in kv]
    near = [x.at[0, i - W + 1].set(100.0) for x in kv]
    assert bool(jnp.any(attend(*near) != base))
    assert bool(jnp.all(attend(*far) == base)) == sliding
