"""`Model.serving_params`: the leaves the step programs read only in the
compute dtype are cast once at placement, not in every step program.

The logits must not move by a bit (here, on the CPU, with one row or
more), no step program may convert a cast leaf again, the leaves the model reads in f32 must keep their dtype, and every
placement the engine derives (construction, `remesh`, the speculative
draft) must serve the cast tree while `_base_params` stays as given."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import ClosedJaxpr, Jaxpr, Literal

from repro.configs import get_config, smoke_variant
from repro.models import layers as model_layers
from repro.models.layers import attach_spiking_ffn_plans
from repro.models.registry import build_model
from repro.serve import Engine, ExecutionPolicy, Placement
from repro.serve.policy import draft
from repro.serve.sharding import make_serve_mesh

SPIKING = dict(spiking_ffn=True, spiking_T=4, spiking_weight_density=0.3)
CASES = {
    "spiking_gqa_plans": ("llama3_2_1b", dict(SPIKING, tie_embeddings=False)),
    "qk_norm": ("qwen3_14b", {}),
    "tied_spiking": ("llama3_2_1b", SPIKING),
    "dense_mlp": ("nemotron_4_340b", {}),
    "moe": ("mixtral_8x22b", {}),
    "vlm": ("llava_next_mistral_7b", {}),
}
ATTN = ("wq", "wk", "wv", "wo")
_BUILT: dict = {}


def _build(case):
    if case not in _BUILT:
        arch, overrides = CASES[case]
        cfg = dataclasses.replace(smoke_variant(get_config(arch)), **overrides)
        model = build_model(cfg)
        _BUILT[case] = (cfg, model, model.init(jax.random.PRNGKey(0)))
    return _BUILT[case]


def _trees(case):
    """The f32 tree and its serving form, as the engine places them
    (join plans attached to both for a spiking FFN)."""
    cfg, model, base = _build(case)
    served = model.serving_params(base)
    if cfg.spiking_ffn:
        return (attach_spiking_ffn_plans(base, cfg),
                attach_spiking_ffn_plans(served, cfg))
    return base, served


class _packed:
    """Trace spiking FFNs in packed-inference mode, as the engine does."""

    def __enter__(self):
        self.prev = model_layers.get_spiking_ffn_mode()
        model_layers.set_spiking_ffn_mode("infer")

    def __exit__(self, *exc):
        model_layers.set_spiking_ffn_mode(self.prev)


def _batch(cfg, B=2, S=8):
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32)}
    if cfg.n_img_tokens:
        batch["img_embed"] = jnp.asarray(
            rng.standard_normal((B, cfg.n_img_tokens, cfg.d_model)), jnp.float32
        )
    return batch


def _greedy_logits(model, cfg, params, rows, steps=3):
    batch = _batch(cfg, B=rows)
    B, S = batch["tokens"].shape
    with _packed():
        logits, cache = jax.jit(model.prefill)(
            params, batch, model.init_cache(B, S + steps)
        )
        out = [logits]
        decode = jax.jit(model.decode)
        for _ in range(steps):
            tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
            logits, cache = decode(params, tok, cache)
            out.append(logits)
    return np.stack([np.asarray(x) for x in out])


def _leaf(tree, *path):
    for k in path:
        tree = tree[k]
    return tree


def _cast_paths(cfg, base):
    """The leaves `serving_params` must cast (given f32 params)."""
    paths = [("layers", "attn", k) for k in ATTN]
    if cfg.n_experts:
        paths += [("layers", "moe", k) for k in base["layers"]["moe"]
                  if k != "router"]
    elif not cfg.spiking_ffn:
        paths += [("layers", "mlp", k) for k in base["layers"]["mlp"]]
    paths += [(k,) for k in ("lm_head", "mm_proj") if k in base]
    return paths


def _kept_paths(cfg, base):
    """Leaves the model reads in f32, or the spiking FFN's own payload
    source: `serving_params` must leave them as they are."""
    paths = [("embed",), ("final_norm",), ("layers", "ln1"), ("layers", "ln2")]
    if cfg.qk_norm:
        paths += [("layers", "attn", "q_norm"), ("layers", "attn", "k_norm")]
    if cfg.n_experts:
        paths.append(("layers", "moe", "router"))
    if cfg.spiking_ffn:
        paths += [("layers", "mlp", "wu"), ("layers", "mlp", "wd")]
    return paths


# ---------------------------------------------------------------------------
# the transform
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rows", [1, 2])
@pytest.mark.parametrize("case", list(CASES))
def test_prefill_and_decode_logits_are_bitwise_unchanged(case, rows):
    cfg, model, _ = _build(case)
    f32, served = _trees(case)
    np.testing.assert_array_equal(
        _greedy_logits(model, cfg, served, rows),
        _greedy_logits(model, cfg, f32, rows),
    )


@pytest.mark.parametrize("case", list(CASES))
def test_cast_leaves_and_kept_leaves(case):
    cfg, model, base = _build(case)
    served = model.serving_params(base)
    assert jax.tree.structure(served) == jax.tree.structure(base)
    for path in _cast_paths(cfg, base):
        assert _leaf(base, *path).dtype == jnp.float32, path
        assert _leaf(served, *path).dtype == jnp.bfloat16, path
        np.testing.assert_array_equal(
            np.asarray(_leaf(served, *path)),
            np.asarray(_leaf(base, *path).astype(jnp.bfloat16)),
        )
    for path in _kept_paths(cfg, base):
        assert _leaf(served, *path) is _leaf(base, *path), path
    # idempotent: a second pass returns every leaf as it is
    again = model.serving_params(served)
    assert all(a is b for a, b in zip(jax.tree.leaves(again),
                                      jax.tree.leaves(served)))


def _converted_reads(jaxpr: Jaxpr, marked: set) -> int:
    """`convert_element_type`s reading a variable in ``marked``, followed
    into nested jaxprs (a sub-jaxpr's inputs are its call's trailing
    operands: scan, pjit, remat, custom_jvp, cond branches, while body)."""
    n = 0
    for eqn in jaxpr.eqns:
        ins = [v in marked if not isinstance(v, Literal) else False
               for v in eqn.invars]
        if eqn.primitive.name == "convert_element_type" and any(ins):
            n += 1
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                sub = sub.jaxpr if isinstance(sub, ClosedJaxpr) else sub
                if not isinstance(sub, Jaxpr):
                    continue
                tail = ins[len(ins) - len(sub.invars):] if sub.invars else []
                inner = {v for v, m in zip(sub.invars, tail) if m}
                n += _converted_reads(sub, inner)
    return n


@pytest.mark.parametrize("case", list(CASES))
def test_decode_step_converts_no_cast_leaf(case):
    cfg, model, base = _build(case)
    f32, served = _trees(case)
    cast = set(_cast_paths(cfg, base))
    tokens = jnp.zeros((2, 1), jnp.int32)

    def reads(tree):
        with _packed():
            closed = jax.make_jaxpr(model.decode)(
                tree, tokens, model.init_cache(2, 8)
            )
        paths = [tuple(k.key for k in path)
                 for path, _ in jax.tree_util.tree_leaves_with_path(tree)]
        invars = closed.jaxpr.invars[:len(paths)]
        return _converted_reads(
            closed.jaxpr, {v for v, p in zip(invars, paths) if p in cast}
        )

    assert reads(f32) > 0          # the search finds the per-call casts
    assert reads(served) == 0


# ---------------------------------------------------------------------------
# the engine: precast_bytes and every placement
# ---------------------------------------------------------------------------

def test_precast_bytes_counts_the_cast_leaves_in_f32():
    cfg, model, base = _build("spiking_gqa_plans")
    eng = Engine(model, base, max_len=16, max_slots=2,
                 policy=ExecutionPolicy.for_arch(cfg))
    want = sum(4 * _leaf(base, *p).size for p in _cast_paths(cfg, base))
    assert want > 0
    assert eng.metrics.precast_bytes == want
    assert eng.summary()["precast_bytes"] == want
    eng.metrics.reset()           # a placement fact, not a window aggregate
    assert eng.summary()["precast_bytes"] == want


@pytest.mark.parametrize("arch,overrides", [
    ("llama3_2_1b", dict(param_dtype="bfloat16")),
    ("rwkv6_1_6b", {}),
], ids=["bf16_params", "rwkv6"])
def test_precast_bytes_is_zero_where_nothing_is_cast(arch, overrides):
    cfg = dataclasses.replace(smoke_variant(get_config(arch)), **overrides)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    served = model.serving_params(params)
    assert all(a is b for a, b in zip(jax.tree.leaves(served),
                                      jax.tree.leaves(params)))
    eng = Engine(model, params, max_len=16, max_slots=2)
    assert eng.summary()["precast_bytes"] == 0


def test_packed_spike_encode_reads_the_f32_embedding():
    cfg, model, base = _build("tied_spiking")
    eng = Engine(model, base, max_len=16, max_slots=2,
                 policy=ExecutionPolicy.for_arch(cfg))
    assert eng.params["embed"] is base["embed"]
    assert eng.params["layers"]["attn"]["wq"].dtype == jnp.bfloat16
    toks = jnp.arange(5, dtype=jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(eng._encode_pack(eng.params, toks)),
        np.asarray(eng._encode_pack(base, toks)),
    )


def _assert_served(params, base, cfg):
    for path in _cast_paths(cfg, base):
        assert _leaf(params, *path).dtype == jnp.bfloat16, path
    for path in _kept_paths(cfg, base):
        assert _leaf(params, *path).dtype == _leaf(base, *path).dtype, path


def _assert_base_untouched(eng, base):
    assert all(a is b for a, b in zip(jax.tree.leaves(eng._base_params),
                                      jax.tree.leaves(base)))


def test_remesh_serves_the_cast_tree_and_keeps_the_base():
    cfg, model, base = _build("spiking_gqa_plans")
    mesh = make_serve_mesh("data=2,model=2", devices=jax.devices()[:4])
    eng = Engine(model, base, max_len=16, max_slots=2,
                 policy=ExecutionPolicy.for_arch(
                     cfg, placement=Placement(mesh=mesh)))
    want = eng.metrics.precast_bytes
    _assert_served(eng.params, base, cfg)
    for devices in (jax.devices()[:6], jax.devices()[:1]):
        assert eng.remesh(devices=devices)["remeshed"]
        _assert_served(eng.params, base, cfg)
        _assert_base_untouched(eng, base)
        assert eng.metrics.precast_bytes == want


def test_speculative_draft_shares_the_cast_tree():
    cfg, model, base = _build("spiking_gqa_plans")
    pol = ExecutionPolicy.for_arch(
        cfg, speculation=draft(ExecutionPolicy.for_arch(cfg), k=2,
                               draft_weight_density=0.2),
    )
    eng = Engine(model, base, max_len=16, max_slots=2, policy=pol)
    _assert_served(eng.draft_params, base, cfg)
    for k in ATTN:        # one bf16 copy, shared by target and draft
        assert (eng.draft_params["layers"]["attn"][k]
                is eng.params["layers"]["attn"][k])
    assert eng.draft_params["lm_head"] is eng.params["lm_head"]
    _assert_base_untouched(eng, base)
