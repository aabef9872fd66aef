"""Batch-composition machinery for the continuous-batching engine.

Every model in the registry exposes its serving cache as a pytree plus a
parallel `cache_axes()` tree of logical-axis tuples (the same trees the
sharding layer consumes).  The engine never hard-codes a cache layout;
instead the helpers here locate the ``"batch"`` axis of every leaf and
concat / gather / pad along it:

* transformer: ``k/v (layers, B, S, kv, dh)`` -> batch axis 1,
  ``kv_pos (S,)`` / ``pos ()`` -> no batch axis (merge invariant: equal).
* rwkv6: ``tm_prev/cm_prev/wkv (L, B, ...)`` -> batch axis 1.
* zamba2 hybrid: nested ``attn`` KV ring inside conv/ssm state.

Leaves without a batch axis are *position-like*: two cohorts may only be
merged when those leaves are identical, which is exactly the "same sequence
length" precondition for continuous batching with a shared scalar position.

Also here: `PackedSpikeCache`, the engine-side store that carries SNN
activations between engine steps as packed uint32 spike words (bit t =
timestep t, LSB = t0) instead of unpacked ``(T, ...)`` float32 planes — the
serving-side continuation of the paper's §IV-A compression argument.

API NOTE: the loose per-operation functions (`cache_concat` / `cache_take`
/ `cache_pad_rows` / `batch_axis_tree`) are DEPRECATED shims.  The engine
and executors consume one `CacheOps` facade instead — `DenseCacheOps`
(this module, the eager concat/gather layout) or
`serve.paging.PagedCacheOps` (page-table edits over a shared page pool) —
so the cache backend is swappable behind ``ExecutionPolicy.paging``.
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np


def _axes_leaves(axes):
    return jax.tree.leaves(axes, is_leaf=lambda x: isinstance(x, tuple))


def _batch_axis_tree(cache, axes) -> list[int | None]:
    cl = jax.tree.leaves(cache)
    al = _axes_leaves(axes)
    if len(cl) != len(al):
        raise ValueError(
            f"cache has {len(cl)} leaves but axes tree has {len(al)}"
        )
    out = []
    for leaf, ax in zip(cl, al):
        if len(ax) != leaf.ndim:
            raise ValueError(f"axes {ax} rank != cache leaf shape {leaf.shape}")
        out.append(ax.index("batch") if "batch" in ax else None)
    return out


def cache_batch_size(cache, axes) -> int:
    """Batch size of a cache pytree (asserts all batched leaves agree)."""
    sizes = {
        leaf.shape[b]
        for leaf, b in zip(jax.tree.leaves(cache), _batch_axis_tree(cache, axes))
        if b is not None
    }
    if len(sizes) != 1:
        raise ValueError(f"inconsistent cache batch sizes {sizes}")
    return sizes.pop()


def _cache_concat(caches: list, axes):
    if len(caches) == 1:
        return caches[0]
    baxes = _batch_axis_tree(caches[0], axes)
    flats = [jax.tree.leaves(c) for c in caches]
    treedef = jax.tree.structure(caches[0])
    out = []
    for i, b in enumerate(baxes):
        leaves = [f[i] for f in flats]
        if b is None:
            first = np.asarray(leaves[0])
            for other in leaves[1:]:
                if not np.array_equal(first, np.asarray(other)):
                    raise ValueError(
                        "refusing to merge cohorts with differing "
                        f"position-like cache leaf (shape {first.shape})"
                    )
            out.append(leaves[0])
        else:
            out.append(jnp.concatenate(leaves, axis=b))
    return jax.tree.unflatten(treedef, out)


def _cache_take(cache, axes, idx):
    idx = jnp.asarray(idx, jnp.int32)
    baxes = _batch_axis_tree(cache, axes)
    leaves = [
        leaf if b is None else jnp.take(leaf, idx, axis=b)
        for leaf, b in zip(jax.tree.leaves(cache), baxes)
    ]
    return jax.tree.unflatten(jax.tree.structure(cache), leaves)


def _cache_pad_rows(cache, axes, n: int):
    if n <= 0:
        return cache
    baxes = _batch_axis_tree(cache, axes)
    leaves = []
    for leaf, b in zip(jax.tree.leaves(cache), baxes):
        if b is None:
            leaves.append(leaf)
            continue
        pad_shape = list(leaf.shape)
        pad_shape[b] = n
        leaves.append(jnp.concatenate(
            [leaf, jnp.zeros(pad_shape, leaf.dtype)], axis=b
        ))
    return jax.tree.unflatten(jax.tree.structure(cache), leaves)


# ---------------------------------------------------------------------------
# CacheOps: the one cache-manipulation surface
# ---------------------------------------------------------------------------

class CacheOps:
    """Facade over cohort-cache manipulation: everything the engine and the
    step executors do to a cache BETWEEN model calls.

    Two backends implement it — `DenseCacheOps` (per-cohort dense pytrees;
    concat/take/pad are whole-cache array ops, the pre-paging layout) and
    `serve.paging.PagedCacheOps` (cohorts hold page tables into a shared
    `CacheStore` pool; the same operations are host page-table edits that
    move no cache data).  The executor never branches on the backend: it
    calls these four methods and the engine's dispatch hooks.
    """

    def batch_size(self, cache) -> int:
        raise NotImplementedError

    def concat(self, caches: list):
        """Merge cohort caches (same sequence position) into one."""
        raise NotImplementedError

    def take(self, cache, idx: list[int]):
        """Keep only rows ``idx`` (host ints); other rows are discarded."""
        raise NotImplementedError

    def pad_rows(self, cache, n: int):
        """Append ``n`` dummy (zero) rows for alignment/rebalance."""
        raise NotImplementedError


class DenseCacheOps(CacheOps):
    """Dense backend: cohort caches are plain pytrees; batch-axis concat /
    gather / zero-pad located via the model's logical-axes tree."""

    def __init__(self, axes_tree):
        self.axes = axes_tree

    def batch_size(self, cache) -> int:
        return cache_batch_size(cache, self.axes)

    def concat(self, caches: list):
        return _cache_concat(caches, self.axes)

    def take(self, cache, idx):
        return _cache_take(cache, self.axes, idx)

    def pad_rows(self, cache, n: int):
        return _cache_pad_rows(cache, self.axes, n)


# ---------------------------------------------------------------------------
# deprecated per-operation shims (the pre-CacheOps surface)
# ---------------------------------------------------------------------------

def _warn_cache_helper(name: str, repl: str):
    warnings.warn(
        f"serve.batching.{name} is deprecated; use {repl} "
        "(serve.batching.DenseCacheOps / serve.paging.PagedCacheOps)",
        DeprecationWarning,
        stacklevel=3,
    )


def batch_axis_tree(cache, axes) -> list[int | None]:
    """DEPRECATED: per-leaf index of the ``"batch"`` axis (None when the
    leaf has no batch dimension), in `jax.tree.leaves` order."""
    _warn_cache_helper("batch_axis_tree", "the CacheOps facade")
    return _batch_axis_tree(cache, axes)


def cache_concat(caches: list, axes):
    """DEPRECATED: merge cohort caches along their batch axes — use
    ``CacheOps.concat``."""
    _warn_cache_helper("cache_concat", "CacheOps.concat")
    return _cache_concat(caches, axes)


def cache_take(cache, axes, idx):
    """DEPRECATED: gather a subset of batch rows — use ``CacheOps.take``."""
    _warn_cache_helper("cache_take", "CacheOps.take")
    return _cache_take(cache, axes, idx)


def cache_pad_rows(cache, axes, n: int):
    """DEPRECATED: append ``n`` zero rows — use ``CacheOps.pad_rows``."""
    _warn_cache_helper("cache_pad_rows", "CacheOps.pad_rows")
    return _cache_pad_rows(cache, axes, n)


def pad_batch(tokens: np.ndarray, align: int) -> tuple[np.ndarray, int]:
    """Pad the *batch* dimension of a (B, S) prompt batch up to a multiple
    of ``align`` with dummy rows (token 0).

    Rows are independent in every registered model's prefill/decode (MoE
    capacity routing excepted — the engine refuses batch padding for MoE),
    so dummy rows never perturb real rows; their outputs are discarded.
    Returns (padded tokens, n_dummy).
    """
    B = tokens.shape[0]
    pad = (-B) % max(1, align)
    if pad == 0:
        return tokens, 0
    dummy = np.zeros((pad, tokens.shape[1]), dtype=tokens.dtype)
    return np.concatenate([tokens, dummy], axis=0), pad


def bucket_key(prompt_len: int, align: int = 1) -> int:
    """Bucket id for a prompt length.

    ``align=1`` buckets by exact length (the engine's default: the models
    have no pad-token masking, so only same-length prompts may share a
    prefill batch without changing results).  Larger ``align`` rounds up —
    an approximate throughput mode for workloads that tolerate pad tokens.
    """
    return -(-prompt_len // max(1, align)) * max(1, align)


# ---------------------------------------------------------------------------
# Packed-spike activation cache
# ---------------------------------------------------------------------------

def spike_sparsity_of(words: np.ndarray, T: int) -> float:
    """Fraction of (neuron, timestep) positions with no spike in a
    (rows, width) batch of packed uint32 words (1.0 for no rows)."""
    words = np.ascontiguousarray(words, np.uint32)
    if words.size == 0:
        return 1.0
    fired = np.unpackbits(
        words.view(np.uint8), bitorder="little"
    ).reshape(words.shape[0], words.shape[1], 32)[..., :T]
    return float(1.0 - fired.mean())


@dataclass
class PackedSpikeCache:
    """Carries per-slot SNN activations between engine steps as packed
    uint32 spike words.

    One row per active slot, ``(width,)`` uint32 each: bit t of word j is
    neuron j's spike at timestep t.  Storing the packed word costs 32 bits
    per neuron regardless of T, vs ``T * 32`` bits for the unpacked float32
    planes the training path carries — the engine reports both so the
    saving shows up in serve metrics.  Slot bookkeeping mirrors the KV
    cache: rows concat on cohort merge and gather on retire.

    Double-buffering (`update_async`): the pipelined executor hands the
    cache the jit'd encode's DEVICE output without waiting on it — the
    encode overlaps the next decode's dispatch, and the device->host copy
    happens lazily at the first telemetry/bookkeeping access (`_sync`).
    """

    T: int
    width: int
    words: np.ndarray = field(init=False)
    _pending_dev: object | None = field(init=False, default=None, repr=False)

    def __post_init__(self):
        self.words = np.zeros((0, self.width), np.uint32)

    def update_async(self, words_dev) -> None:
        """Stage this step's (B, width) device words WITHOUT materializing
        them; a later `update_async` before any access just replaces the
        buffer (only the newest step's words matter — `update` semantics)."""
        self._pending_dev = words_dev

    def _sync(self) -> None:
        if self._pending_dev is not None:
            pending, self._pending_dev = self._pending_dev, None
            self.update(np.asarray(pending))

    def __len__(self) -> int:
        self._sync()
        return self.words.shape[0]

    def append(self, words) -> None:
        self._sync()
        w = np.asarray(words, np.uint32).reshape(-1, self.width)
        self.words = np.concatenate([self.words, w], axis=0)

    def update(self, words) -> None:
        """Replace all slots' words with this step's (B, width) batch."""
        self._sync()
        w = np.asarray(words, np.uint32).reshape(-1, self.width)
        if w.shape[0] != len(self):
            raise ValueError(f"update of {w.shape[0]} rows into {len(self)} slots")
        self.words = w

    def merge(self, other: "PackedSpikeCache") -> None:
        if (other.T, other.width) != (self.T, self.width):
            raise ValueError("merging incompatible spike caches")
        self._sync()
        other._sync()
        self.words = np.concatenate([self.words, other.words], axis=0)

    def take(self, idx) -> None:
        self._sync()
        self.words = self.words[np.asarray(idx, np.int64)]

    def latest(self):
        """The newest words, without a device-to-host copy: the staged
        device words while an async update is pending, else the host
        words."""
        if self._pending_dev is not None:
            return self._pending_dev
        return self.words

    def spike_sparsity(self) -> float:
        """Fraction of (neuron, timestep) positions with no spike."""
        self._sync()
        return spike_sparsity_of(self.words, self.T)

    def silent_fraction(self) -> float:
        """Fraction of silent neurons (word == 0) — droppable entirely."""
        self._sync()
        if self.words.size == 0:
            return 1.0
        return float((self.words == 0).mean())

    def nbytes_packed(self) -> int:
        self._sync()
        return int(self.words.nbytes)

    def nbytes_unpacked_f32(self) -> int:
        self._sync()
        return int(self.words.shape[0] * self.width * self.T * 4)
