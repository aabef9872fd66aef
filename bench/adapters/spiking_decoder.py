"""Decoder LM with spiking FFNs: the program's config and seeded weights.

`arch_config` maps a configuration file (Hugging Face key names) onto the
program's `ArchConfig`; `make_weights` draws every weight on the device in
one jitted call from the seed, in the program's parameter layout and at
the configuration's parameter dtype.  The reference (`bench/reference/`)
reads the same arrays, so the weights are the benchmark's, not the
program's.  Nothing here imports the program at module level.
"""
from __future__ import annotations

import functools

import numpy as np


def arch_config(conf: dict):
    """The program's `ArchConfig` for a configuration file."""
    from repro.configs.base import ArchConfig

    sp = conf["spiking"]
    return ArchConfig(
        name=conf["name"], family="dense",
        n_layers=conf["num_hidden_layers"],
        d_model=conf["hidden_size"], d_ff=conf["intermediate_size"],
        vocab=conf["vocab_size"], n_heads=conf["num_attention_heads"],
        n_kv=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        qk_norm=conf["qk_norm"], attn="causal",
        rope_theta=conf["rope_theta"], norm_eps=conf["rms_norm_eps"],
        tie_embeddings=conf["tie_word_embeddings"],
        spiking_ffn=True, spiking_T=sp["T"],
        spiking_weight_density=sp["weight_density"],
        param_dtype=conf["dtypes"]["params"],
        compute_dtype=conf["dtypes"]["compute"],
    )


def check_program_constants(conf: dict) -> None:
    """The program's LIF constants and block grid are not settable; refuse
    a configuration that states other ones."""
    from repro.core.lif import DEFAULT_TAU, DEFAULT_VTH
    from repro.kernels.join_plan import BK, BN

    sp = conf["spiking"]
    if (sp["v_th"], sp["tau"]) != (DEFAULT_VTH, DEFAULT_TAU):
        raise SystemExit(f"{conf['name']}: v_th/tau {sp['v_th']}/{sp['tau']}"
                         f" but the program runs {DEFAULT_VTH}/{DEFAULT_TAU}")
    if tuple(sp["block"]) != (BK, BN):
        raise SystemExit(f"{conf['name']}: block {sp['block']} but the "
                         f"program's join plans use {(BK, BN)}")


def jax_key(seed: int):
    """A JAX key from any non-negative seed (wider than 32 bits too)."""
    import jax

    return jax.random.key(int(np.random.SeedSequence(seed).generate_state(1)[0]))


def shapes(conf: dict) -> dict:
    """Leaf shapes of the parameter tree, in the program's layout."""
    D, F, V = conf["hidden_size"], conf["intermediate_size"], conf["vocab_size"]
    L, H = conf["num_hidden_layers"], conf["num_attention_heads"]
    KV, dh = conf["num_key_value_heads"], conf["head_dim"]
    attn = {"wq": (L, D, H * dh), "wk": (L, D, KV * dh),
            "wv": (L, D, KV * dh), "wo": (L, H * dh, D)}
    if conf["qk_norm"]:
        attn.update(q_norm=(L, dh), k_norm=(L, dh))
    return {
        "embed": (V, D),
        "layers": {"ln1": (L, D), "attn": attn, "ln2": (L, D),
                   "mlp": {"wu": (L, D, F), "wd": (L, F, D)}},
        "final_norm": (D,),
        "lm_head": (D, V),
    }


def block_counts(L, nkb, nnb, density, which: int) -> np.ndarray:
    """(L, nnb) kept k-blocks per output column-block: the column counts
    of round(density * blocks) blocks chosen at random, drawn once with a
    fixed generator (not the run's seed).  Every seed then keeps the same
    multiset of counts, so the join plans have the same shapes (longest
    column, nonzero blocks) and the same work, and no seed compiles
    programs of its own; the seed permutes the columns and picks which
    k-blocks each keeps."""
    rng = np.random.default_rng([0, which])
    total = int(round(density * nkb * nnb))
    return np.stack([
        np.bincount(rng.permutation(nkb * nnb)[:total] % nnb, minlength=nnb)
        for _ in range(L)])


def _block_mask(key, L, K, N, block, density, which):
    """(L, K, N) {0,1} block mask with `block_counts` blocks per column."""
    import jax
    import jax.numpy as jnp

    bk, bn = block
    nkb, nnb = K // bk, N // bn
    counts = jnp.asarray(block_counts(L, nkb, nnb, density, which))
    kp, ks = jax.random.split(key)
    perm = jax.vmap(lambda k: jax.random.permutation(k, nnb))(
        jax.random.split(kp, L))
    counts = jnp.take_along_axis(counts, perm, axis=1)          # (L, nnb)
    score = jax.random.uniform(ks, (L, nkb, nnb))
    rank = jnp.argsort(jnp.argsort(score, axis=1), axis=1)
    m = (rank < counts[:, None, :]).reshape(L, nkb, 1, nnb, 1)
    return jnp.broadcast_to(m, (L, nkb, bk, nnb, bn)).reshape(L, K, N)


@functools.lru_cache(maxsize=None)
def _maker(conf_key: str):
    import json

    import jax
    import jax.numpy as jnp

    conf = json.loads(conf_key)
    dt = jnp.dtype(conf["dtypes"]["params"])
    shp = shapes(conf)
    sp = conf["spiking"]
    D = conf["hidden_size"]

    def make(key):
        names = ["embed", "wq", "wk", "wv", "wo", "wu", "wd", "lm_head",
                 "mu", "md"]
        ks = dict(zip(names, jax.random.split(key, len(names))))

        def normal(name, shape, fan_in):
            w = jax.random.normal(ks[name], shape, jnp.float32)
            return (w / np.sqrt(fan_in)).astype(dt)

        lay = shp["layers"]
        attn = {n: normal(n, lay["attn"][n], lay["attn"][n][1])
                for n in ("wq", "wk", "wv", "wo")}
        for n in ("q_norm", "k_norm"):
            if n in lay["attn"]:
                attn[n] = jnp.zeros(lay["attn"][n], dt)
        mlp = {}
        for which, (n, m) in enumerate((("wu", "mu"), ("wd", "md"))):
            L, K, N = lay["mlp"][n]
            mask = _block_mask(ks[m], L, K, N, sp["block"],
                               sp["weight_density"], which)
            mlp[n] = (normal(n, (L, K, N), K) * mask).astype(dt)
        return {
            "embed": normal("embed", shp["embed"], D),
            "layers": {"ln1": jnp.zeros(lay["ln1"], dt), "attn": attn,
                       "ln2": jnp.zeros(lay["ln2"], dt), "mlp": mlp},
            "final_norm": jnp.zeros(shp["final_norm"], dt),
            "lm_head": normal("lm_head", shp["lm_head"], D),
        }

    return jax.jit(make)


def make_weights(conf: dict, seed: int) -> dict:
    """Every weight of the configuration, drawn on the device from ``seed``."""
    import json

    return _maker(json.dumps(conf, sort_keys=True))(jax_key(seed))
