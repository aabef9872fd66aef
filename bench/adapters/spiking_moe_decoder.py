"""Decoder LM with spiking dual-sparse experts and mixed sliding/full
attention: the program's config and seeded weights.

`arch_config` maps a configuration file (Hugging Face key names, as
Mellum2's ``config.json``) onto the program's `ArchConfig`; `make_weights`
draws every weight on the device in one jitted call from the seed, in the
program's parameter layout and at the configuration's parameter dtype.
The reference (`bench/reference/spiking_moe_decoder.py`) reads the same
arrays.  Nothing here imports the program at module level.
"""
from __future__ import annotations

import functools
import importlib.util
import sys
from pathlib import Path


def _dense():
    """The dense decoder's adapter, whose seeding and block masks the
    experts share."""
    name = "adapter_spiking_decoder"
    if name not in sys.modules:
        path = Path(__file__).with_name("spiking_decoder.py")
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


def layer_types(conf: dict) -> tuple:
    """The attention kinds of the layers this stage holds."""
    return tuple(conf["layer_types"][: conf["num_hidden_layers"]])


def arch_config(conf: dict):
    """The program's `ArchConfig` for a configuration file."""
    from repro.configs.base import ArchConfig

    sp = conf["spiking"]
    yarn = conf["rope_parameters"]["full_attention"]
    plain = conf["rope_parameters"]["sliding_attention"]
    if yarn["rope_theta"] != plain["rope_theta"] or yarn["rope_type"] != "yarn":
        raise SystemExit(f"{conf['name']}: the program runs one rope_theta "
                         "and YaRN on the full layers")
    return ArchConfig(
        name=conf["name"], family="moe",
        n_layers=conf["num_hidden_layers"],
        d_model=conf["hidden_size"], d_ff=conf["intermediate_size"],
        vocab=conf["vocab_size"], n_heads=conf["num_attention_heads"],
        n_kv=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        attn="causal", window=conf["sliding_window"],
        layer_types=layer_types(conf),
        rope_theta=float(plain["rope_theta"]),
        yarn_factor=float(yarn["factor"]),
        yarn_original_max_pos=yarn["original_max_position_embeddings"],
        yarn_beta_fast=float(yarn["beta_fast"]),
        yarn_beta_slow=float(yarn["beta_slow"]),
        yarn_attention_factor=float(yarn["attention_factor"]),
        norm_eps=conf["rms_norm_eps"],
        tie_embeddings=conf["tie_word_embeddings"],
        n_experts=conf["num_experts"], top_k=conf["num_experts_per_tok"],
        d_expert=conf["moe_intermediate_size"],
        spiking_ffn=True, spiking_T=sp["T"],
        spiking_weight_density=sp["weight_density"],
        param_dtype=conf["dtypes"]["params"],
        compute_dtype=conf["dtypes"]["compute"],
    )


def check_program_constants(conf: dict) -> None:
    """As the dense adapter's, the routing the program runs, and whether
    the program takes this architecture at all (before any weight is
    drawn, so a program without it fails at once)."""
    _dense().check_program_constants(conf)
    if not conf["norm_topk_prob"]:
        raise SystemExit(f"{conf['name']}: the program renormalises the "
                         "top-k gates")
    try:
        arch_config(conf)
    except TypeError as e:
        raise SystemExit(f"{conf['name']}: the program has no spiking-"
                         f"expert, mixed-attention architecture ({e})")


def shapes(conf: dict) -> dict:
    """Leaf shapes of the parameter tree, in the program's layout."""
    D, V = conf["hidden_size"], conf["vocab_size"]
    L, H = conf["num_hidden_layers"], conf["num_attention_heads"]
    KV, dh = conf["num_key_value_heads"], conf["head_dim"]
    E, F = conf["num_experts"], conf["moe_intermediate_size"]
    return {
        "embed": (V, D),
        "layers": {
            "ln1": (L, D), "ln2": (L, D),
            "attn": {"wq": (L, D, H * dh), "wk": (L, D, KV * dh),
                     "wv": (L, D, KV * dh), "wo": (L, H * dh, D)},
            "moe": {"router": (L, D, E), "wu": (L, E, D, F),
                    "wd": (L, E, F, D)}},
        "final_norm": (D,),
        "lm_head": (D, V),
    }


@functools.lru_cache(maxsize=None)
def _maker(conf_key: str):
    import json

    import jax
    import jax.numpy as jnp
    import numpy as np

    dense = _dense()
    conf = json.loads(conf_key)
    dt = jnp.dtype(conf["dtypes"]["params"])
    shp = shapes(conf)
    sp = conf["spiking"]
    D = conf["hidden_size"]

    def make(key):
        names = ["embed", "wq", "wk", "wv", "wo", "router", "wu", "wd",
                 "lm_head", "mu", "md"]
        ks = dict(zip(names, jax.random.split(key, len(names))))

        def normal(name, shape, fan_in, dtype=dt):
            w = jax.random.normal(ks[name], shape, jnp.float32)
            return (w / np.sqrt(fan_in)).astype(dtype)

        lay = shp["layers"]
        attn = {n: normal(n, s, s[1]) for n, s in lay["attn"].items()}
        moe = {"router": normal("router", lay["moe"]["router"], D,
                                jnp.float32)}
        for which, (n, m) in enumerate((("wu", "mu"), ("wd", "md"))):
            L, E, K, N = lay["moe"][n]
            mask = dense._block_mask(ks[m], L * E, K, N, sp["block"],
                                     sp["weight_density"], which)
            w = normal(n, (L * E, K, N), K) * mask.astype(dt)
            moe[n] = w.reshape(L, E, K, N)
        return {
            "embed": normal("embed", shp["embed"], D),
            "layers": {"ln1": jnp.zeros(lay["ln1"], dt), "attn": attn,
                       "ln2": jnp.zeros(lay["ln2"], dt), "moe": moe},
            "final_norm": jnp.zeros(shp["final_norm"], dt),
            "lm_head": normal("lm_head", shp["lm_head"], D),
        }

    return jax.jit(make)


def make_weights(conf: dict, seed: int) -> dict:
    """Every weight of the configuration, drawn on the device from ``seed``."""
    import json

    return _maker(json.dumps(conf, sort_keys=True))(_dense().jax_key(seed))
