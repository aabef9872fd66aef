"""Paged cache storage + radix prefix reuse for the serving engine.

The dense serving layout (``paging='none'``) gives every cohort its own
cache pytree, so continuous batching pays whole-cache array traffic at
every membership change: merge is a batch-axis `concatenate` of both
cohorts' full KV, retire a full `take` of the survivors, rebalance a full
zero-pad.  That is exactly the memory-traffic tax the paper's dataflow
argument targets ("fetch once, reuse across the temporal loop", PAPER.md
§4) — applied here at the serving layer instead of the kernel loop.

``paging='paged'`` stores cache state in fixed MXU-aligned pages owned by
one engine-wide `CacheStore`:

* every *sequence* leaf (logical axes contain ``"batch"`` and
  ``"cache_seq"``: transformer/zamba ``k``/``v``) is cut into
  ``page_size``-position pages, pooled as ``(n_pages, ..., page_size,
  ...)`` per leaf;
* every *state* leaf (``"batch"`` without ``"cache_seq"``: rwkv
  ``tm_prev``/``cm_prev``/``wkv``, zamba ``conv``/``ssm``) is one page per
  row in its own pool;
* *position-like* leaves (no batch axis: ``kv_pos``/``pos``) stay
  per-cohort "locals" — the same merge-invariant scalars the dense layout
  shares.

A cohort then holds a `PagedCache`: host page TABLES (``(B, pages_per_row)``
sequence-page ids + ``(B,)`` state-page ids) plus the locals.  Cohort
merge/retire/rebalance become page-table edits — `PagedCacheOps` below
moves **zero** cache bytes for them (`EngineMetrics.n_page_moves` stays 0,
asserted by tests).  Model code is untouched: each jit'd prefill/decode
call gathers the tables into a dense view that is **bitwise identical** to
the dense layout's cache (gather/scatter are pure data movement — no
arithmetic — so every bitwise policy keeps token identity), runs the
unchanged model function, and scatters back only the pages the step wrote
(prefill: all of the row's pages; decode: the single active page per row,
located from the traced ring position — no retrace).

On top of the store sits `RadixPrefixIndex`: a page-chunk trie of published
prompt prefixes.  `Scheduler.submit` hashes the prompt; an exact
full-prompt hit admits the request into a cohort with the shared KV pages
ref-counted in place (zero prefill compute for the shared prefix) and a
copy-on-write clone of the divergence (tail) page — the only page the new
request will write.  Causal attention makes the shared pages valid: ``k``/
``v`` at position *i* depend only on tokens ``<= i``, so identical token
prefixes produce bitwise-identical KV pages.  State leaves and the
position locals depend on the *whole* prompt, so hits are full-prompt
exact matches (hash + token verification — a hash collision can never
serve wrong pages) and entries snapshot the post-prefill state page and
locals plus the deterministic greedy first token.
"""
from __future__ import annotations

import zlib
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from .batching import CacheOps, _axes_leaves, spike_sparsity_of


class PagePoolExhausted(RuntimeError):
    """The page pool ran out even after evicting every unpinned prefix
    entry — the engine needs a larger ``page_pool_rows``."""


# ---------------------------------------------------------------------------
# PageLayout: leaf classification + gather/scatter + paged model wrappers
# ---------------------------------------------------------------------------

class PageLayout:
    """Paging schema for one model's cache pytree.

    Built from a batch-1 template cache and the model's logical-axes tree;
    classifies every leaf (sequence / state / local), derives the pooled
    page shapes, and builds the jit-able paged prefill/decode wrappers the
    engine compiles.  All rearrangement is reshape/transpose/gather —
    bitwise-exact data movement.
    """

    def __init__(self, template, axes_tree, page_size: int):
        self.page_size = int(page_size)
        self.treedef = jax.tree.structure(template)
        leaves = jax.tree.leaves(template)
        axes = _axes_leaves(axes_tree)
        if len(leaves) != len(axes):
            raise ValueError(
                f"cache has {len(leaves)} leaves but axes tree has {len(axes)}"
            )
        # per-leaf: ("seq", b, s) | ("state", b) | ("local",)
        self.kinds: list[tuple] = []
        self.page_shapes: dict[str, tuple] = {}   # pool key -> (shape, dtype)
        self.seq_keys: list[str] = []
        self.state_keys: list[str] = []
        self.local_idx: list[int] = []
        self._pos_local: int | None = None        # index into locals list
        extents = set()
        for i, (leaf, ax) in enumerate(zip(leaves, axes)):
            if len(ax) != leaf.ndim:
                raise ValueError(
                    f"axes {ax} rank != cache leaf shape {leaf.shape}"
                )
            key = f"l{i}"
            if "batch" in ax and "cache_seq" in ax:
                b, s = ax.index("batch"), ax.index("cache_seq")
                extents.add(leaf.shape[s])
                pd = [d for j, d in enumerate(leaf.shape) if j != b]
                sp = s - (1 if b < s else 0)
                pd[sp] = self.page_size
                self.kinds.append(("seq", b, s, sp))
                self.page_shapes[key] = (tuple(pd), leaf.dtype)
                self.seq_keys.append(key)
            elif "batch" in ax:
                b = ax.index("batch")
                pd = tuple(d for j, d in enumerate(leaf.shape) if j != b)
                self.kinds.append(("state", b))
                self.page_shapes[key] = (pd, leaf.dtype)
                self.state_keys.append(key)
            else:
                self.kinds.append(("local",))
                if leaf.ndim == 0 and self._pos_local is None:
                    self._pos_local = len(self.local_idx)
                self.local_idx.append(i)
        if len(extents) > 1:
            raise ValueError(
                f"paged serving needs one cache_seq extent, got {sorted(extents)}"
                " (mixed-window caches are not pageable)"
            )
        self.seq_extent = extents.pop() if extents else 0
        if self.seq_extent % self.page_size:
            raise ValueError(
                f"cache sequence extent {self.seq_extent} is not a multiple "
                f"of paging.page_size {self.page_size}; pick a page size "
                "that divides it (or round max_len up)"
            )
        self.pages_per_row = self.seq_extent // self.page_size
        self.has_state = bool(self.state_keys)
        if self.seq_extent and self._pos_local is None:
            raise ValueError(
                "paged serving needs a scalar position local to locate the "
                "active page; this cache has none"
            )

    # -- per-leaf gather/scatter (pure data movement) -----------------------
    @jax.named_scope("kv_gather")
    def _gather_leaves(self, pools, seq_table, state_table, locals_):
        """Rebuild the dense cache view from the pools (bitwise equal to
        the dense layout's cache for the same history)."""
        B = seq_table.shape[0] if self.seq_extent else state_table.shape[0]
        P = self.pages_per_row
        out, li, si = [], iter(self.local_idx), 0
        loc = list(locals_)
        for i, kind in enumerate(self.kinds):
            key = f"l{i}"
            if kind[0] == "seq":
                _, b, s, sp = kind
                pd = self.page_shapes[key][0]
                g = pools[key][seq_table.reshape(-1)]
                g = g.reshape(B, P, *pd)
                g = jnp.moveaxis(g, 1, 1 + sp)
                shape = (B, *pd[:sp], self.seq_extent, *pd[sp + 1:])
                g = g.reshape(shape)
                out.append(jnp.moveaxis(g, 0, b))
            elif kind[0] == "state":
                b = kind[1]
                g = pools[key][state_table]
                out.append(jnp.moveaxis(g, 0, b))
            else:
                out.append(loc.pop(0))
        return jax.tree.unflatten(self.treedef, out)

    def _locals_of(self, cache):
        leaves = jax.tree.leaves(cache)
        return [leaves[i] for i in self.local_idx]

    @jax.named_scope("kv_scatter")
    def _scatter_all(self, pools, cache, seq_table, state_table):
        """Write every page of every row (prefill: the whole view is new,
        including the zero tail — so freshly allocated pages need no
        separate zeroing)."""
        P = self.pages_per_row
        leaves = jax.tree.leaves(cache)
        pools = dict(pools)
        for i, kind in enumerate(self.kinds):
            key = f"l{i}"
            if kind[0] == "seq":
                _, b, s, sp = kind
                pd = self.page_shapes[key][0]
                x = jnp.moveaxis(leaves[i], b, 0)
                B = x.shape[0]
                x = x.reshape(B, *pd[:sp], P, self.page_size, *pd[sp + 1:])
                x = jnp.moveaxis(x, 1 + sp, 1)
                x = x.reshape(B * P, *pd)
                pools[key] = pools[key].at[seq_table.reshape(-1)].set(x)
            elif kind[0] == "state":
                x = jnp.moveaxis(leaves[i], kind[1], 0)
                pools[key] = pools[key].at[state_table].set(x)
        return pools

    @jax.named_scope("kv_scatter")
    def _scatter_step(self, pools, cache, seq_table, state_table, pos,
                      span: int = 1):
        """Write back one decode dispatch: the sequence pages the write of
        ``span`` positions starting at the traced ring position can have
        touched, plus the state pages (rewritten every dispatch).

        ``span`` is static at trace time (the decode window: 1 for plain
        decode, k+1 for a speculative verify, the chain length for a fused
        propose).  Worst-case page-boundary alignment makes a span of S
        straddle ``(S-1)//page_size + 2`` pages; page indices past the row
        end are clamped to the last page, whose extra write is idempotent
        (the gathered view equals pool content wherever the model wrote
        nothing), and clamping only ever aims HIGHER pages — never the
        low-index pages a shared prefix lives in."""
        leaves = jax.tree.leaves(cache)
        pools = dict(pools)
        if self.seq_extent:
            slot = pos.astype(jnp.int32) % self.seq_extent
            first = slot // self.page_size
            n_pages = min(self.pages_per_row,
                          (int(span) - 1) // self.page_size + 2)
        for i, kind in enumerate(self.kinds):
            key = f"l{i}"
            if kind[0] == "seq":
                _, b, s, sp = kind
                x = jnp.moveaxis(leaves[i], b, 0)
                for j in range(n_pages):
                    active = jnp.minimum(first + j, self.pages_per_row - 1)
                    ids = jnp.take(seq_table, active, axis=1)  # (B,) pages
                    chunk = jax.lax.dynamic_slice_in_dim(
                        x, active * self.page_size, self.page_size,
                        axis=1 + sp,
                    )
                    pools[key] = pools[key].at[ids].set(chunk)
            elif kind[0] == "state":
                x = jnp.moveaxis(leaves[i], kind[1], 0)
                pools[key] = pools[key].at[state_table].set(x)
        return pools

    # -- jit-able model wrappers -------------------------------------------
    def make_prefill(self, model, max_len: int, mesh=None, axes_tree=None):
        """(params, tokens, pools, seq_table, state_table) ->
        (logits, pools, locals).  The view starts from the model's own
        zero-initialized cache — exactly the dense prefill."""
        constrain = _view_constrainer(mesh, axes_tree)

        def fn(params, tokens, pools, seq_table, state_table):
            cache = model.init_cache(tokens.shape[0], max_len)
            logits, cache = model.prefill(params, {"tokens": tokens}, cache)
            cache = constrain(cache)
            pools = self._scatter_all(pools, cache, seq_table, state_table)
            return logits, pools, self._locals_of(cache)

        return fn

    def make_decode(self, model, mesh=None, axes_tree=None):
        """(params, tokens, pools, seq_table, state_table, locals) ->
        (logits, pools, locals).  Tokens may be (B, 1) plain decode or a
        wider (B, S) window (speculative verify / stream frame chunk) — the
        span scatter covers every page the window wrote."""
        constrain = _view_constrainer(mesh, axes_tree)

        def fn(params, tokens, pools, seq_table, state_table, locals_):
            cache = self._gather_leaves(pools, seq_table, state_table, locals_)
            cache = constrain(cache)
            pos = (locals_[self._pos_local]
                   if self._pos_local is not None else None)
            logits, cache = model.decode(params, tokens, cache)
            pools = self._scatter_step(
                pools, cache, seq_table, state_table, pos,
                span=tokens.shape[1],
            )
            return logits, pools, self._locals_of(cache)

        return fn

    def make_propose(self, model, k: int, catchup: int, mesh=None,
                     axes_tree=None):
        """Paged fused draft-propose: (params, chunk, pools, seq_table,
        state_table, locals) -> (draft_tokens (B, k), pools, locals).

        ``chunk`` is (B, catchup) host-known tokens: the pending token,
        preceded by the already-verified catch-up token when the draft cache
        is one position behind (the previous round accepted everything).
        One gather, ``catchup - 1`` catch-up positions + ``k`` chained
        greedy steps with on-device argmax feedback, one span scatter — a
        single dispatch regardless of k."""
        constrain = _view_constrainer(mesh, axes_tree)
        span = k + catchup - 1

        def fn(params, chunk, pools, seq_table, state_table, locals_):
            cache = self._gather_leaves(pools, seq_table, state_table, locals_)
            cache = constrain(cache)
            pos = (locals_[self._pos_local]
                   if self._pos_local is not None else None)
            if catchup > 1:
                _, cache = model.decode(params, chunk[:, : catchup - 1], cache)
            tok = chunk[:, catchup - 1]
            out = []
            for _ in range(k):
                logits, cache = model.decode(params, tok[:, None], cache)
                tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
                out.append(tok)
            pools = self._scatter_step(
                pools, cache, seq_table, state_table, pos, span=span
            )
            return jnp.stack(out, axis=1), pools, self._locals_of(cache)

        return fn


def _view_constrainer(mesh, axes_tree):
    """Pin the gathered dense view to the canonical per-leaf cache sharding
    inside the jit (mirrors `sharding.place_cache` — data movement only)."""
    if mesh is None or axes_tree is None:
        return lambda cache: cache
    from .sharding import cache_sharding

    def constrain(cache):
        return jax.tree.map(
            lambda leaf, ax: jax.lax.with_sharding_constraint(
                leaf, cache_sharding(leaf, ax, mesh)
            ),
            cache,
            axes_tree,
            is_leaf=lambda x: isinstance(x, tuple),
        )

    return constrain


# ---------------------------------------------------------------------------
# CacheStore: pooled pages + alloc/free/ref-count
# ---------------------------------------------------------------------------

def _pool_copy(pool, src, dst):
    return pool.at[dst].set(pool[src])


def _pool_zero(pool, ids):
    return pool.at[ids].set(0)


class CacheStore:
    """Engine-wide owner of the page pools.

    One device pool array per paged cache leaf (page axis leading), one
    shared logical page-id space per *kind* — every sequence pool is
    indexed by the same sequence-page id, every state pool by the same
    state-page id — so a row's allocation is ``pages_per_row`` sequence ids
    plus one state id, and ref-counting/free lists are per-kind host
    arrays, not per-leaf.

    ``n_page_moves`` counts page-granular COPIES (prefix publish snapshots
    and copy-on-write clones).  Merge/retire/rebalance go through
    `PagedCacheOps` and never copy — the zero-page-move invariant the
    tests assert.
    """

    def __init__(self, layout: PageLayout, n_rows: int, mesh=None,
                 metrics=None):
        if n_rows < 1:
            raise ValueError("page pool needs at least one row")
        self.layout = layout
        self.mesh = mesh
        self.metrics = metrics
        self.on_pressure = None   # callable(kind) -> bool: try to free pages
        self.n_seq_pages = max(1, n_rows * max(1, layout.pages_per_row))
        self.n_state_pages = max(1, n_rows)
        self.pools = {}
        for key in layout.seq_keys:
            shape, dtype = layout.page_shapes[key]
            self.pools[key] = jnp.zeros((self.n_seq_pages, *shape), dtype)
        for key in layout.state_keys:
            shape, dtype = layout.page_shapes[key]
            self.pools[key] = jnp.zeros((self.n_state_pages, *shape), dtype)
        if mesh is not None:
            from .sharding import place_pool

            self.pools = {
                k: place_pool(v, mesh) for k, v in self.pools.items()
            }
        self._seq_ref = np.zeros(self.n_seq_pages, np.int32)
        self._state_ref = np.zeros(self.n_state_pages, np.int32)
        self._seq_free = list(range(self.n_seq_pages - 1, -1, -1))
        self._state_free = list(range(self.n_state_pages - 1, -1, -1))

    # -- allocation ---------------------------------------------------------
    def _alloc(self, free: list, ref: np.ndarray, n: int, kind: str):
        while len(free) < n:
            if self.on_pressure is None or not self.on_pressure(kind):
                raise PagePoolExhausted(
                    f"page pool out of {kind} pages (need {n}, "
                    f"free {len(free)}); raise Engine(page_pool_rows=...)"
                )
        ids = np.asarray([free.pop() for _ in range(n)], np.int32)
        ref[ids] = 1
        return ids

    def alloc_seq(self, n: int) -> np.ndarray:
        return self._alloc(self._seq_free, self._seq_ref, n, "seq")

    def alloc_state(self, n: int) -> np.ndarray:
        return self._alloc(self._state_free, self._state_ref, n, "state")

    def alloc_rows(self, n_rows: int) -> tuple[np.ndarray, np.ndarray]:
        """(seq_table (n, pages_per_row), state_table (n,)) for fresh rows.
        Pages are NOT zeroed — cold prefill scatters every page of the row."""
        P = self.layout.pages_per_row
        seq = self.alloc_seq(n_rows * P).reshape(n_rows, P)
        state = (self.alloc_state(n_rows) if self.layout.has_state
                 else np.zeros(n_rows, np.int32))
        return seq, state

    def alloc_rows_zeroed(self, n_rows: int) -> tuple[np.ndarray, np.ndarray]:
        """Fresh rows with ZEROED pages — for dummy/rebalance rows and the
        unwritten tail of prefix-hit rows, where the gather must read the
        same zeros the dense layout would hold."""
        seq, state = self.alloc_rows(n_rows)
        self.zero_seq(seq.reshape(-1))
        if self.layout.has_state:
            self.zero_state(state)
        return seq, state

    # -- ref-counting -------------------------------------------------------
    def incref_seq(self, ids) -> None:
        self._seq_ref[np.asarray(ids, np.int32)] += 1

    def _decref(self, free: list, ref: np.ndarray, ids) -> None:
        for i in np.asarray(ids, np.int32).reshape(-1):
            ref[i] -= 1
            if ref[i] == 0:
                free.append(int(i))
            elif ref[i] < 0:
                raise RuntimeError(f"page {int(i)} double-freed")

    def decref_seq(self, ids) -> None:
        self._decref(self._seq_free, self._seq_ref, ids)

    def decref_state(self, ids) -> None:
        if self.layout.has_state:
            self._decref(self._state_free, self._state_ref, ids)

    def seq_refcount(self, page: int) -> int:
        return int(self._seq_ref[page])

    @property
    def free_seq_pages(self) -> int:
        return len(self._seq_free)

    @property
    def free_state_pages(self) -> int:
        return len(self._state_free)

    # -- page data ops (the ONLY movers of cache bytes outside model calls) -
    def _count_moves(self, n: int) -> None:
        if self.metrics is not None:
            self.metrics.n_page_moves += n

    def copy_seq(self, src, dst) -> None:
        src = jnp.asarray(src, jnp.int32)
        dst = jnp.asarray(dst, jnp.int32)
        for key in self.layout.seq_keys:
            self.pools[key] = _pool_copy(self.pools[key], src, dst)
        self._count_moves(int(src.shape[0]))

    def copy_state(self, src, dst) -> None:
        src = jnp.asarray(src, jnp.int32)
        dst = jnp.asarray(dst, jnp.int32)
        for key in self.layout.state_keys:
            self.pools[key] = _pool_copy(self.pools[key], src, dst)
        self._count_moves(int(src.shape[0]))

    def zero_seq(self, ids) -> None:
        ids = jnp.asarray(ids, jnp.int32)
        for key in self.layout.seq_keys:
            self.pools[key] = _pool_zero(self.pools[key], ids)

    def zero_state(self, ids) -> None:
        ids = jnp.asarray(ids, jnp.int32)
        for key in self.layout.state_keys:
            self.pools[key] = _pool_zero(self.pools[key], ids)

    def summary(self) -> dict:
        return {
            "page_size": self.layout.page_size,
            "pages_per_row": self.layout.pages_per_row,
            "seq_pages_total": self.n_seq_pages,
            "seq_pages_free": self.free_seq_pages,
            "state_pages_total": (self.n_state_pages
                                  if self.layout.has_state else 0),
            "state_pages_free": (self.free_state_pages
                                 if self.layout.has_state else 0),
        }


# ---------------------------------------------------------------------------
# PagedCache + PagedCacheOps
# ---------------------------------------------------------------------------

@dataclass
class PagedCache:
    """A cohort's cache under ``paging='paged'``: host page tables into the
    engine's `CacheStore` plus the per-cohort position locals (device)."""

    store: CacheStore
    seq_table: np.ndarray     # (B, pages_per_row) int32
    state_table: np.ndarray   # (B,) int32
    locals: list              # device arrays, layout.local_idx order

    @property
    def batch(self) -> int:
        return int(self.state_table.shape[0])

    def release(self) -> None:
        """Drop every row (decref; shared pages survive via their refs)."""
        self.store.decref_seq(self.seq_table)
        self.store.decref_state(self.state_table)
        self.seq_table = self.seq_table[:0]
        self.state_table = self.state_table[:0]


class PagedCacheOps(CacheOps):
    """Paged backend of the cache-manipulation facade: every operation is
    a host page-table edit.  No pool bytes move (``n_page_moves`` untouched)
    — pad_rows allocates fresh zeroed pages (a write of zeros, not a copy
    of cache state, mirroring the dense layout's zero rows)."""

    def __init__(self, store: CacheStore):
        self.store = store

    def batch_size(self, cache: PagedCache) -> int:
        return cache.batch

    def concat(self, caches: list) -> PagedCache:
        if len(caches) == 1:
            return caches[0]
        first = caches[0]
        for other in caches[1:]:
            for a, b in zip(first.locals, other.locals):
                if not np.array_equal(np.asarray(a), np.asarray(b)):
                    raise ValueError(
                        "refusing to merge cohorts with differing "
                        "position-like cache locals"
                    )
        return PagedCache(
            store=self.store,
            seq_table=np.concatenate([c.seq_table for c in caches], axis=0),
            state_table=np.concatenate(
                [c.state_table for c in caches], axis=0
            ),
            locals=first.locals,
        )

    def take(self, cache: PagedCache, idx) -> PagedCache:
        idx = np.asarray(idx, np.int64)
        keep = np.zeros(cache.batch, bool)
        keep[idx] = True
        for r in np.nonzero(~keep)[0]:
            self.store.decref_seq(cache.seq_table[r])
            self.store.decref_state(cache.state_table[r : r + 1])
        return PagedCache(
            store=self.store,
            seq_table=cache.seq_table[idx],
            state_table=cache.state_table[idx],
            locals=cache.locals,
        )

    def pad_rows(self, cache: PagedCache, n: int) -> PagedCache:
        if n <= 0:
            return cache
        seq, state = self.store.alloc_rows_zeroed(n)
        return PagedCache(
            store=self.store,
            seq_table=np.concatenate([cache.seq_table, seq], axis=0),
            state_table=np.concatenate([cache.state_table, state], axis=0),
            locals=cache.locals,
        )


# ---------------------------------------------------------------------------
# Paged packed-spike cache
# ---------------------------------------------------------------------------

class SpikeSlotPool:
    """Host pool of packed-spike rows (one ``(width,)`` uint32 word row per
    engine slot), so cohort merge/take are id-list edits like the KV
    tables instead of `np.concatenate` copies."""

    def __init__(self, width: int, n_rows: int):
        self.words = np.zeros((n_rows, width), np.uint32)
        self._free = list(range(n_rows - 1, -1, -1))

    def alloc(self, n: int) -> np.ndarray:
        if len(self._free) < n:
            raise PagePoolExhausted(
                f"spike slot pool out of rows (need {n}, free "
                f"{len(self._free)})"
            )
        return np.asarray([self._free.pop() for _ in range(n)], np.int64)

    def free(self, ids) -> None:
        self._free.extend(int(i) for i in np.asarray(ids).reshape(-1))


class PagedSpikeCache:
    """`PackedSpikeCache`-interface view over a shared `SpikeSlotPool`.

    Same double-buffering contract (`update_async`/`_sync`) and telemetry;
    `merge`/`take` edit the row-id list instead of concatenating/gathering
    the word arrays.
    """

    def __init__(self, T: int, width: int, pool: SpikeSlotPool):
        self.T, self.width, self.pool = T, width, pool
        self.row_ids = np.zeros((0,), np.int64)
        self._pending_dev = None

    @property
    def words(self) -> np.ndarray:
        self._sync()
        return self.pool.words[self.row_ids]

    def update_async(self, words_dev) -> None:
        self._pending_dev = words_dev

    def _sync(self) -> None:
        if self._pending_dev is not None:
            pending, self._pending_dev = self._pending_dev, None
            self.update(np.asarray(pending))

    def __len__(self) -> int:
        self._sync()
        return int(self.row_ids.shape[0])

    def append(self, words) -> None:
        self._sync()
        w = np.asarray(words, np.uint32).reshape(-1, self.width)
        ids = self.pool.alloc(w.shape[0])
        self.pool.words[ids] = w
        self.row_ids = np.concatenate([self.row_ids, ids])

    def update(self, words) -> None:
        self._sync()
        w = np.asarray(words, np.uint32).reshape(-1, self.width)
        if w.shape[0] != len(self):
            raise ValueError(
                f"update of {w.shape[0]} rows into {len(self)} slots"
            )
        self.pool.words[self.row_ids] = w

    def merge(self, other: "PagedSpikeCache") -> None:
        if (other.T, other.width) != (self.T, self.width):
            raise ValueError("merging incompatible spike caches")
        if other.pool is not self.pool:
            raise ValueError("merging spike caches from different pools")
        self._sync()
        other._sync()
        self.row_ids = np.concatenate([self.row_ids, other.row_ids])
        other.row_ids = other.row_ids[:0]

    def take(self, idx) -> None:
        self._sync()
        idx = np.asarray(idx, np.int64)
        keep = np.zeros(self.row_ids.shape[0], bool)
        keep[idx] = True
        self.pool.free(self.row_ids[~keep])
        self.row_ids = self.row_ids[idx]

    def latest(self):
        if self._pending_dev is not None:
            return self._pending_dev
        return self.words

    # -- telemetry (same formulas as PackedSpikeCache) ----------------------
    def spike_sparsity(self) -> float:
        return spike_sparsity_of(self.words, self.T)

    def silent_fraction(self) -> float:
        w = self.words
        if w.size == 0:
            return 1.0
        return float((w == 0).mean())

    def nbytes_packed(self) -> int:
        return int(self.words.nbytes)

    def nbytes_unpacked_f32(self) -> int:
        return int(len(self) * self.width * self.T * 4)


# ---------------------------------------------------------------------------
# Radix prefix index
# ---------------------------------------------------------------------------

@dataclass
class PrefixEntry:
    """One published full-prompt prefix.

    ``full_pages`` are trie-node sequence pages shared by ref-count;
    ``tail_page`` is the index-owned snapshot of the divergence page (the
    page a hit's decode will write — cloned again, copy-on-write, at
    admission); ``state_page`` the index-owned post-prefill state snapshot;
    ``locals_np`` the post-prefill position locals; ``first_token`` the
    deterministic greedy first token the prefill emitted.
    """

    prompt: np.ndarray
    full_pages: np.ndarray            # (n_full_chunks,) int32
    tail_page: int | None
    state_page: int | None
    locals_np: list
    first_token: int
    last_used: int = 0
    pins: int = 0                     # queued hits not yet admitted
    alive: bool = True

    @property
    def prompt_len(self) -> int:
        return int(self.prompt.shape[0])


class _TrieNode:
    __slots__ = ("children", "page", "n_entries")

    def __init__(self, page: int | None = None):
        self.children: dict[int, list] = {}   # hash -> [(chunk_bytes, node)]
        self.page = page
        self.n_entries = 0

    def find(self, h: int, chunk: bytes):
        for cb, node in self.children.get(h, ()):
            if cb == chunk:
                return node
        return None

    def add(self, h: int, chunk: bytes, node: "_TrieNode") -> None:
        self.children.setdefault(h, []).append((chunk, node))

    def remove(self, h: int, chunk: bytes) -> None:
        lst = self.children.get(h, [])
        self.children[h] = [(cb, n) for cb, n in lst if cb != chunk]
        if not self.children[h]:
            del self.children[h]


class RadixPrefixIndex:
    """Page-chunk radix trie over published prompt prefixes.

    * **Dedup**: prompts sharing leading ``page_size``-token chunks share
      trie nodes — and therefore share the underlying KV pages (one
      ref-count hold per node, however many entries pass through it).
    * **Collision safety**: both the trie children and the full-prompt
      entry buckets are keyed by hash *and verified by token equality* —
      a colliding hash can cost a lookup miss, never a wrong page.
    * **Eviction**: least-recently-used entries are dropped when
      ``max_entries`` is hit or when the `CacheStore` runs out of pages
      (the store's pressure hook); entries with queued-but-unadmitted hits
      are pinned and never evicted.
    """

    def __init__(self, store: CacheStore, *, max_entries: int = 32):
        self.store = store
        self.page_size = store.layout.page_size
        self.max_entries = max_entries
        self.root = _TrieNode()
        self._buckets: dict[int, list[PrefixEntry]] = {}
        self._paths: dict[int, list] = {}   # id(entry) -> trie path
        self._tick = 0
        self.n_lookups = 0
        self.n_hits = 0
        store.on_pressure = self._on_pressure

    @staticmethod
    def _hash(data: bytes) -> int:
        return zlib.crc32(data)

    def __len__(self) -> int:
        return sum(len(v) for v in self._buckets.values())

    @property
    def entries(self) -> list[PrefixEntry]:
        return [e for v in self._buckets.values() for e in v]

    # -- lookup -------------------------------------------------------------
    def lookup(self, prompt: np.ndarray) -> PrefixEntry | None:
        """Exact full-prompt match (hash bucket + token verification)."""
        self.n_lookups += 1
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        h = self._hash(prompt.tobytes())
        for e in self._buckets.get(h, ()):
            if e.alive and np.array_equal(e.prompt, prompt):
                self._tick += 1
                e.last_used = self._tick
                self.n_hits += 1
                return e
        return None

    # -- publish ------------------------------------------------------------
    def publish(self, prompt, seq_row, state_id, locals_np,
                first_token: int) -> PrefixEntry | None:
        """Publish one just-prefilled row's prefix.

        ``seq_row``: the row's (pages_per_row,) sequence-page ids (their
        full-chunk prefix is shared by incref; the partial tail page is
        snapshot-copied — it is about to be written by the row's own
        decode).  Returns None when the prompt is already published or the
        pool cannot hold the snapshot.
        """
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        h = self._hash(prompt.tobytes())
        for e in self._buckets.get(h, ()):
            if e.alive and np.array_equal(e.prompt, prompt):
                return None
        while len(self) >= self.max_entries:
            if not self.evict_lru():
                return None
        ps = self.page_size
        P = prompt.shape[0]
        # state-only caches (rwkv) have no sequence pages: the reusable
        # prefix is entirely the state-page snapshot + locals (the trie
        # holds the entry but shares no pages)
        paged_seq = self.store.layout.pages_per_row > 0
        n_full = P // ps if paged_seq else 0
        has_tail = paged_seq and bool(P % ps)
        # snapshot copies FIRST (they can fail under pool pressure; trie
        # increfs cannot) — a failed publish leaves no trace
        try:
            tail = None
            if has_tail:
                tail = int(self.store.alloc_seq(1)[0])
                self.store.copy_seq([int(seq_row[n_full])], [tail])
            state = None
            if self.store.layout.has_state:
                state = int(self.store.alloc_state(1)[0])
                self.store.copy_state([int(state_id)], [state])
        except PagePoolExhausted:
            if has_tail and tail is not None:
                self.store.decref_seq([tail])
            return None
        # walk/extend the trie over the full chunks, sharing nodes (and
        # their pages) with previously published prompts
        node, path, full_pages = self.root, [], []
        for c in range(n_full):
            chunk = prompt[c * ps : (c + 1) * ps].tobytes()
            ch = self._hash(chunk)
            child = node.find(ch, chunk)
            if child is None:
                page = int(seq_row[c])
                self.store.incref_seq([page])
                child = _TrieNode(page)
                node.add(ch, chunk, child)
            child.n_entries += 1
            path.append((node, ch, chunk, child))
            full_pages.append(child.page)
            node = child
        self._tick += 1
        entry = PrefixEntry(
            prompt=prompt.copy(),
            full_pages=np.asarray(full_pages, np.int32),
            tail_page=tail,
            state_page=state,
            locals_np=[np.asarray(x) for x in locals_np],
            first_token=int(first_token),
            last_used=self._tick,
        )
        self._buckets.setdefault(h, []).append(entry)
        self._paths[id(entry)] = path
        return entry

    # -- admission ----------------------------------------------------------
    def admit(self, entry: PrefixEntry) -> tuple[np.ndarray, np.ndarray]:
        """Materialize one row from a prefix entry: incref the shared full
        pages in place, copy-on-write the divergence (tail) page, allocate
        zeroed pages for the unwritten rest of the row, and clone the
        state page.  Returns (seq_row (pages_per_row,), state_id (1,))."""
        if not entry.alive:
            raise RuntimeError("prefix entry was evicted while queued")
        store, ps = self.store, self.page_size
        layout = store.layout
        n_full = entry.prompt_len // ps if layout.pages_per_row else 0
        n_rest = layout.pages_per_row - n_full
        # pin across the allocations: their pressure evictions must not pick
        # THIS entry (the engine pins queued hits, but direct callers may
        # not), and a failed allocation must roll every hold back
        entry.pins += 1
        store.incref_seq(entry.full_pages)
        fresh = None
        try:
            if n_rest:
                fresh = store.alloc_seq(n_rest)
            state = (np.zeros(1, np.int32) if not layout.has_state
                     else store.alloc_state(1))
        except PagePoolExhausted:
            store.decref_seq(entry.full_pages)
            if fresh is not None:
                store.decref_seq(fresh)
            raise
        finally:
            entry.pins -= 1
        row = np.zeros(layout.pages_per_row, np.int32)
        row[:n_full] = entry.full_pages
        if n_rest:
            store.zero_seq(fresh)
            row[n_full:] = fresh
            if entry.tail_page is not None:
                store.copy_seq([entry.tail_page], [int(row[n_full])])
        if entry.state_page is not None:
            store.copy_state([entry.state_page], state)
        return row, state

    # -- eviction -----------------------------------------------------------
    def evict_lru(self) -> bool:
        """Drop the least-recently-used unpinned entry; True if one went."""
        victim = None
        for e in self.entries:
            if e.pins == 0 and (victim is None
                                or e.last_used < victim.last_used):
                victim = e
        if victim is None:
            return False
        self._evict(victim)
        return True

    def _evict(self, entry: PrefixEntry) -> None:
        entry.alive = False
        h = self._hash(entry.prompt.tobytes())
        self._buckets[h] = [e for e in self._buckets.get(h, [])
                            if e is not entry]
        if not self._buckets[h]:
            del self._buckets[h]
        if entry.tail_page is not None:
            self.store.decref_seq([entry.tail_page])
        if entry.state_page is not None:
            self.store.decref_state([entry.state_page])
        # release trie nodes bottom-up once no entry passes through them
        for parent, ch, chunk, node in reversed(
            self._paths.pop(id(entry), [])
        ):
            node.n_entries -= 1
            if node.n_entries == 0 and not node.children:
                self.store.decref_seq([node.page])
                parent.remove(ch, chunk)

    def _on_pressure(self, kind: str) -> bool:
        return self.evict_lru()

    def summary(self) -> dict:
        return {
            "entries": len(self),
            "lookups": self.n_lookups,
            "hits": self.n_hits,
            "hit_rate": self.n_hits / max(1, self.n_lookups),
        }
