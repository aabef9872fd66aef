"""Policy-dispatched jit'd wrappers around the Pallas FTP kernels.

One front door: ``dispatch(a, weights_or_plan, policy, T)`` routes by the
`repro.serve.policy.ExecutionPolicy` and the operand type —

* ``spike_format='float'``   -> the differentiable jnp reference path
  ((T, M, K) float spikes; no Pallas);
* ``spike_format='packed'`` + dense weights -> the dense-weight FTP kernels
  (batched entry when ``a`` has a leading batch axis; the mesh-parallel
  shard_map entry when the policy's placement carries a mesh);
* ``spike_format='packed'`` + a `WeightJoinPlan` -> the dual-sparse BSR
  kernel (load-time weight join + device-side spike join; sharded plans
  dispatch through shard_map under the policy/serve mesh);
* ``weight_sparsity='dual_sparse'`` + raw (pruned) weights -> convenience:
  plan built per call, then the BSR kernel;
* a plan stacked over experts + ``groups`` -> the grouped BSR kernel over
  rows sorted and padded by expert (`group_rows`; spiking-MoE layers).

The wrappers handle padding to MXU-aligned blocks and backend dispatch
(interpret=True off-TPU so the kernels are validated everywhere; compiled on
real TPUs).  Per-request spike activity is a pure value change: no host work
and no retrace across requests (`BSR_TRACE_COUNT` counts traces so callers
can assert the latter).

The pre-policy entry points (``ftp_spmm``, ``ftp_spmm_fused_lif``,
``ftp_spmm_bsr`` and friends) are gone — `dispatch` with the equivalent
policy is the only door (they spent two PRs as DeprecationWarning shims;
CI runs tier-1 with ``-W error::DeprecationWarning``, so no caller could
still be on them).
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.lif import DEFAULT_TAU, DEFAULT_VTH
from repro.core.packing import (
    block_activity_map,
    mask_low_activity_timesteps,
    timestep_activity_map,
)

from . import ftp_spmm as _k
from .join_plan import (
    ShardedWeightJoinPlan,
    WeightJoinPlan,
    build_block_csr,
    build_weight_plan,
    stack_plans,
)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# Serve-mesh context: the serving engine scopes a (data, model) mesh around
# its jit'd prefill/decode calls (read at TRACE time, like the spiking-FFN
# mode).  Under an active mesh, the BSR path dispatches plans that carry a
# leading model-shard axis (join_plan.shard_plan) through a shard_map whose
# row axis is `data` (request batch) and whose column axis is `model` (plan
# column slabs) — each model shard joins only its own slab of the static
# weight plan against the device-local spike activity map.  `dispatch` with
# a policy whose placement carries a mesh installs that mesh for the call.
# ---------------------------------------------------------------------------

_SERVE_MESH = None


def set_serve_mesh(mesh) -> None:
    """Install (or clear, with None) the serving mesh the sharded kernel
    entry points close over."""
    global _SERVE_MESH
    _SERVE_MESH = mesh


def get_serve_mesh():
    return _SERVE_MESH


@contextlib.contextmanager
def serve_mesh_scope(mesh):
    prev = _SERVE_MESH
    set_serve_mesh(mesh)
    try:
        yield mesh
    finally:
        set_serve_mesh(prev)


def _row_axis(mesh, M: int) -> str | None:
    """Shard kernel rows over `data` when the row count divides the axis
    (cohorts shrink as requests retire; non-divisible batches fall back to
    replicated rows — a placement change only, never a numerics change)."""
    dn = mesh.shape.get("data", 1)
    return "data" if (dn > 1 and M % dn == 0) else None


def _pad_to(x: jax.Array, mults: tuple[int, ...]) -> jax.Array:
    pads = [(0, (-s) % m) for s, m in zip(x.shape, mults)]
    if any(p[1] for p in pads):
        return jnp.pad(x, pads)
    return x


def _row_block(M: int, bm: int = _k.BM) -> int:
    """Row block for ``M`` kernel rows: ``bm``, shrunk for small problems
    to ``M`` rounded up to the 8-row f32 sublane tile (rows are padded up
    to it, so a 12-row decode batch runs as one 16-row block)."""
    return min(bm, -(-max(8, M) // 8) * 8)


def _pick_blocks(M, K, N, bm, bk, bn):
    """Shrink default blocks for small problems (still 8/128-aligned when
    possible; interpret mode accepts anything)."""
    return _row_block(M, bm), min(bk, max(8, K)), min(bn, max(128, N) if N >= 128 else N)


# ---------------------------------------------------------------------------
# Dense-weight internals (canonical implementations; `dispatch` is the
# public API, the legacy names below are deprecated shims over these).
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("T", "bm", "bk", "bn", "interpret"))
def _spmm(
    a_packed, b, T: int, *, bm=_k.BM, bk=_k.BK, bn=_k.BN, interpret=None
):
    """(M, K) uint32 x (K, N) -> (T, M, N) f32 (dense-weight FTP kernel)."""
    interpret = (not _on_tpu()) if interpret is None else interpret
    M, K = a_packed.shape
    N = b.shape[1]
    bm, bk, bn = _pick_blocks(M, K, N, bm, bk, bn)
    ap = _pad_to(a_packed, (bm, bk))
    bp = _pad_to(b, (bk, bn))
    out = _k.ftp_spmm(ap, bp, T, bm=bm, bk=bk, bn=bn, interpret=interpret)
    return out[:, :M, :N]


@functools.partial(
    jax.jit, static_argnames=("T", "v_th", "tau", "bm", "bk", "bn", "interpret")
)
def _spmm_fused(
    a_packed,
    b,
    T: int,
    v_th: float = DEFAULT_VTH,
    tau: float = DEFAULT_TAU,
    *,
    bm=_k.BM,
    bk=_k.BK,
    bn=_k.BN,
    interpret=None,
):
    """(M, K) uint32 x (K, N) -> ((M, N) uint32, (M, N) f32) fused LoAS layer."""
    interpret = (not _on_tpu()) if interpret is None else interpret
    M, K = a_packed.shape
    N = b.shape[1]
    bm, bk, bn = _pick_blocks(M, K, N, bm, bk, bn)
    ap = _pad_to(a_packed, (bm, bk))
    bp = _pad_to(b, (bk, bn))
    c, u = _k.ftp_spmm_fused_lif(
        ap, bp, T, v_th, tau, bm=bm, bk=bk, bn=bn, interpret=interpret
    )
    return c[:M, :N], u[:M, :N]


# Batched entries (serving): a (B, M, K) packed batch is one (B*M, K) x
# (K, N) problem — the kernels are row-parallel, so folding the batch into
# the row dimension is exact and keeps the MXU grid dense.  The weight tile
# is fetched once and reused across the whole batch (and all T timesteps),
# which is where continuous batching compounds the paper's weight-traffic
# amortization.

@functools.partial(jax.jit, static_argnames=("T", "bm", "bk", "bn", "interpret"))
def _spmm_batched(
    a_packed, b, T: int, *, bm=_k.BM, bk=_k.BK, bn=_k.BN, interpret=None
):
    """(B, M, K) uint32 x (K, N) -> (T, B, M, N) f32."""
    B, M, K = a_packed.shape
    out = _spmm(
        a_packed.reshape(B * M, K), b, T,
        bm=bm, bk=bk, bn=bn, interpret=interpret,
    )
    return out.reshape(T, B, M, b.shape[1])


@functools.partial(
    jax.jit, static_argnames=("T", "v_th", "tau", "bm", "bk", "bn", "interpret")
)
def _spmm_fused_batched(
    a_packed,
    b,
    T: int,
    v_th: float = DEFAULT_VTH,
    tau: float = DEFAULT_TAU,
    *,
    bm=_k.BM,
    bk=_k.BK,
    bn=_k.BN,
    interpret=None,
):
    """(B, M, K) uint32 x (K, N) -> ((B, M, N) uint32, (B, M, N) f32)."""
    B, M, K = a_packed.shape
    c, u = _spmm_fused(
        a_packed.reshape(B * M, K), b, T, v_th, tau,
        bm=bm, bk=bk, bn=bn, interpret=interpret,
    )
    N = b.shape[1]
    return c.reshape(B, M, N), u.reshape(B, M, N)


@functools.partial(
    jax.jit, static_argnames=("T", "bm", "bk", "bn", "interpret", "mesh")
)
def _spmm_sharded(a_packed, b, T, bm, bk, bn, interpret, mesh):
    M = a_packed.shape[0]
    row = _row_axis(mesh, M)

    def body(a_loc, b_loc):
        return _spmm(a_loc, b_loc, T, bm=bm, bk=bk, bn=bn,
                     interpret=interpret)

    out = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(row, None), P(None, "model")),
        out_specs=P(None, row, "model"),
        check_vma=False,
    )(a_packed, b)
    # gather columns back to the canonical layout (see _bsr_call_sharded)
    return jax.lax.with_sharding_constraint(
        out, NamedSharding(mesh, P(None, row, None))
    )


def _spmm_mesh(
    a_packed, b, T: int, *, mesh=None,
    bm=_k.BM, bk=_k.BK, bn=_k.BN, interpret=None,
):
    """Mesh-aware dense-weight FTP entry: weight columns on `model`, spike
    rows on `data` (when divisible) — each shard runs the plain kernel on
    its (row-block, column-slab) tile; the full-K contraction per output
    element stays inside one shard, so the result equals the unsharded
    `_spmm` exactly.  Falls back to the single-device wrapper when no mesh
    is active or the column count does not divide the model axis."""
    mesh = get_serve_mesh() if mesh is None else mesh
    interpret = (not _on_tpu()) if interpret is None else interpret
    if mesh is None:
        return _spmm(a_packed, b, T, bm=bm, bk=bk, bn=bn,
                     interpret=interpret)
    mp = mesh.shape.get("model", 1)
    if mp > 1 and b.shape[1] % mp:
        return _spmm(a_packed, b, T, bm=bm, bk=bk, bn=bn,
                     interpret=interpret)
    return _spmm_sharded(a_packed, b, T, bm, bk, bn, interpret, mesh)


# ---------------------------------------------------------------------------
# Dual-sparse internals: load-time weight join plan + device-side spike join.
#
# The weight side of the block-level inner join is static per model and lives
# in a `WeightJoinPlan` (kernels/join_plan.py) built ONCE at load; the spike
# side is a per-request `block_activity_map` computed ON DEVICE inside the
# jit'd wrapper.  A change in spike activity between calls is a pure value
# change — same shapes, no host join, no retrace (`BSR_TRACE_COUNT` exposes
# the trace count so tests/serving can assert this).
# ---------------------------------------------------------------------------

# Incremented each time the BSR wrapper is TRACED (not called).  After
# warm-up, serving steps with changing spike activity must leave it constant.
BSR_TRACE_COUNT = 0


@functools.partial(
    jax.jit,
    static_argnames=(
        "T", "v_th", "tau", "bm", "n_out", "fuse_lif", "interpret",
        "adaptive", "min_spikes",
    ),
)
def _bsr_call(
    a_packed, plan, T, v_th, tau, bm, n_out, fuse_lif, interpret,
    adaptive=False, min_spikes=1,
):
    global BSR_TRACE_COUNT
    BSR_TRACE_COUNT += 1  # trace-time side effect, by design
    M, K = a_packed.shape
    if K > plan.k_padded:
        raise ValueError(
            f"spike width {K} exceeds plan K {plan.k_padded}"
        )
    pads = [(0, (-M) % bm), (0, plan.k_padded - K)]
    ap = jnp.pad(a_packed, pads) if any(p for _, p in pads) else a_packed
    # Device-side spike join: the activity map never leaves the accelerator.
    act = block_activity_map(ap, bm, plan.bk).astype(jnp.int32)
    # Temporal third of the join (policy temporal='adaptive'): score each
    # timestep bit-plane on device; planes below min_spikes skip their MXU
    # work in-kernel.  Like `act`, a change in which planes are silent is a
    # pure value change — same shapes, zero retrace.
    tmap = (
        timestep_activity_map(ap, T, min_spikes).astype(jnp.int32)
        if adaptive
        else None
    )
    c, u = _k.ftp_spmm_bsr(
        ap,
        plan.payload,
        plan.kidx,
        plan.vidx,
        plan.cnt,
        act,
        plan.n_padded,
        T,
        v_th,
        tau,
        tmap=tmap,
        bm=bm,
        fuse_lif=fuse_lif,
        interpret=interpret,
    )
    if fuse_lif:
        return c[:M, :n_out], u[:M, :n_out]
    return c[:, :M, :n_out], u[:M, :n_out]


@functools.partial(
    jax.jit,
    static_argnames=(
        "T", "v_th", "tau", "bm", "n_out", "fuse_lif", "interpret", "mesh",
        "adaptive", "min_spikes",
    ),
)
def _bsr_call_sharded(
    a_packed, plan, T, v_th, tau, bm, n_out, fuse_lif, interpret, mesh,
    adaptive=False, min_spikes=1,
):
    """shard_map entry for the BSR kernel: plan column slabs on `model`,
    spike rows on `data` (when divisible).

    Each (data, model) shard pads its local rows, computes its own spike
    block-activity map, and joins it against its own k/n-block slab of the
    static plan — a full-K contraction per output column inside one shard,
    so concatenating slabs equals the unsharded kernel bit-for-bit (no
    cross-shard reduction).  Per-request spike activity stays a pure value
    change: same shapes, same shardings, zero retrace.

    Under ``adaptive`` each shard also scores its LOCAL timestep planes.
    At min_spikes=1 this stays bitwise: a plane silent over a shard's rows
    contributes exactly zero to that shard's outputs whether or not other
    shards fire at that timestep.  min_spikes>1 thresholds per-shard counts
    (approximate by policy anyway, drift gated by exactness tol).
    """
    global BSR_TRACE_COUNT
    BSR_TRACE_COUNT += 1  # trace-time side effect, by design (see _bsr_call)
    M = a_packed.shape[0]
    row = _row_axis(mesh, M)

    def body(a_loc, plan_loc):
        plan_l = jax.tree.map(lambda x: x[0], plan_loc)
        # caller-supplied bm is honored; default adapts to the LOCAL row
        # count (rows are already divided over `data` here)
        bm_l = _row_block(a_loc.shape[0]) if bm is None else bm
        return _bsr_call(
            a_loc, plan_l, T, v_th, tau, bm_l, plan_l.n_padded, fuse_lif,
            interpret, adaptive=adaptive, min_spikes=min_spikes,
        )

    c_spec = P(row, "model") if fuse_lif else P(None, row, "model")
    c, u = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(row, None), P("model")),
        out_specs=(c_spec, P(row, "model")),
        check_vma=False,  # no replication rule for pallas_call
    )(a_packed, plan)
    # Gather the column slabs back to the canonical activation layout (rows
    # on `data`, features replicated) RIGHT HERE: without this, the 'model'
    # sharding of the hidden dim propagates into the residual stream (and,
    # under lax.scan, into the layer carry), where GSPMD then partitions
    # attention contractions with psum — reassociating bf16 sums and
    # breaking the token-identity contract.
    gather = lambda x, spec: jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, spec)
    )
    u = gather(u, P(row, None))[:, :n_out]
    if fuse_lif:
        return gather(c, P(row, None))[:, :n_out], u
    return gather(c, P(None, row, None))[:, :, :n_out], u


@functools.partial(
    jax.jit,
    static_argnames=(
        "T", "v_th", "tau", "bm", "n_out", "fuse_lif", "interpret", "mesh",
        "adaptive", "min_spikes",
    ),
)
def _bsr_call_rows(
    a_packed, plan, T, v_th, tau, bm, n_out, fuse_lif, interpret, mesh,
    adaptive=False, min_spikes=1,
):
    """shard_map entry for a whole (unsplit) plan under a multi-device mesh:
    the plan replicated, spike rows on `data` (when divisible).  Mosaic
    kernels cannot be partitioned automatically, so even a mesh with no
    model split runs the kernel per shard.  Rows are independent, so this
    is the single-device result bit for bit; only adaptive min_spikes>1
    scores each shard's own rows, as `_bsr_call_sharded` does."""
    row = _row_axis(mesh, a_packed.shape[0])

    def body(a_loc, plan_loc):
        bm_l = _row_block(a_loc.shape[0]) if bm is None else bm
        return _bsr_call(
            a_loc, plan_loc, T, v_th, tau, bm_l, n_out, fuse_lif, interpret,
            adaptive=adaptive, min_spikes=min_spikes,
        )

    c_spec = P(row, None) if fuse_lif else P(None, row, None)
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(row, None), P()),
        out_specs=(c_spec, P(row, None)),
        check_vma=False,  # no replication rule for pallas_call
    )(a_packed, plan)


def _bsr(
    a_packed,
    plan,
    T: int,
    v_th: float = DEFAULT_VTH,
    tau: float = DEFAULT_TAU,
    *,
    bm: int | None = None,
    n_out: int | None = None,
    fuse_lif: bool = True,
    interpret: bool | None = None,
    adaptive: bool = False,
    min_spikes: int = 1,
):
    """Dual-sparse FTP spMspM against a load-time `WeightJoinPlan`.

    a_packed: (M, K) uint32 packed spikes; plan: WeightJoinPlan built once
    from the pruned weights.  Returns (packed spikes (M, n_out), U) when
    ``fuse_lif`` else ((T, M, n_out) full sums, zeros) — without the LIF
    epilogue there are no membrane potentials.  Fully jit'd; per-request
    work is device-only.

    Under an active serve mesh (`set_serve_mesh` / the engine's scope /
    `dispatch` with a mesh placement), a plan carrying a leading model-shard
    axis (`join_plan.shard_plan`) dispatches to the shard_map entry: each
    model shard joins its own column slab of the static plan against the
    device-local activity map.  A whole plan under a multi-device mesh runs
    per data shard of the rows (`_bsr_call_rows`).
    """
    interpret = (not _on_tpu()) if interpret is None else interpret
    mesh = get_serve_mesh()
    if mesh is not None and isinstance(plan, ShardedWeightJoinPlan):
        mp = mesh.shape.get("model", 1)
        if plan.payload.ndim != 4:
            raise ValueError(
                "sharded dispatch needs a per-layer plan (payload rank 4); "
                f"got rank {plan.payload.ndim} — slice the layer axis first"
            )
        if plan.payload.shape[0] != mp:
            raise ValueError(
                f"plan has {plan.payload.shape[0]} column slabs but mesh "
                f"model axis is {mp}; build with join_plan.shard_plan(plan, {mp})"
            )
        n_out = mp * plan.n_padded if n_out is None else n_out
        return _bsr_call_sharded(
            a_packed, plan, T, v_th, tau, bm, n_out, fuse_lif, interpret,
            mesh, adaptive=adaptive, min_spikes=min_spikes,
        )
    n_out = plan.n_padded if n_out is None else n_out
    if mesh is not None and mesh.size > 1:
        return _bsr_call_rows(
            a_packed, plan, T, v_th, tau, bm, n_out, fuse_lif, interpret,
            mesh, adaptive=adaptive, min_spikes=min_spikes,
        )
    bm = _row_block(a_packed.shape[0]) if bm is None else bm
    return _bsr_call(
        a_packed, plan, T, v_th, tau, bm, n_out, fuse_lif, interpret,
        adaptive=adaptive, min_spikes=min_spikes,
    )


def _bsr_batched(
    a_packed,
    plan,
    T: int,
    v_th: float = DEFAULT_VTH,
    tau: float = DEFAULT_TAU,
    *,
    bm: int | None = None,
    n_out: int | None = None,
    fuse_lif: bool = True,
    interpret: bool | None = None,
    adaptive: bool = False,
    min_spikes: int = 1,
):
    """(B, M, K) batched dual-sparse entry — the batch folds into rows (same
    trick as `_spmm_batched`), so one weight-plan fetch serves the whole
    batch and all T timesteps.  Temporal scoring under ``adaptive`` is then
    over the folded batch: a timestep is skipped only when silent across
    EVERY request in the batch (conservative, and what keeps min_spikes=1
    bitwise per request)."""
    B, M, K = a_packed.shape
    out, u = _bsr(
        a_packed.reshape(B * M, K), plan, T, v_th, tau,
        bm=bm, n_out=n_out, fuse_lif=fuse_lif, interpret=interpret,
        adaptive=adaptive, min_spikes=min_spikes,
    )
    N = out.shape[-1]
    if fuse_lif:
        return out.reshape(B, M, N), u.reshape(B, M, N)
    return out.reshape(T, B, M, N), u.reshape(B, M, N)


# ---------------------------------------------------------------------------
# Grouped dual-sparse entry: the routed rows of a spiking-MoE layer, sorted
# and padded by expert (`group_rows`), through one BSR call per GEMM over
# all experts' plans (stacked on a leading expert axis).
# ---------------------------------------------------------------------------

def grouped_row_block(rows: int, n_groups: int) -> int:
    """Row tile for ``rows`` routed rows over ``n_groups`` experts: the
    mean rows per expert, rounded up to the 8-row sublane tile, at most
    `_k.BM` (a one-token decode runs 8-row tiles, a long prefill 128)."""
    return _row_block(-(-rows // n_groups))


def grouped_tiles(rows: int, n_groups: int, bm: int) -> int:
    """Row tiles enough for any routing of ``rows`` rows over ``n_groups``
    experts, each expert's rows padded to ``bm``: a static bound, so the
    grid never depends on the routing."""
    return (rows + min(n_groups, rows) * (bm - 1)) // bm


def group_rows(eidx, n_groups: int, bm: int):
    """Sort routed (row, slot) pairs by expert and pad each expert's rows
    to ``bm``, on the device.

    eidx: (N, k) int expert of each pair.  Returns ``src`` ((nm * bm,)
    int32: the input row of each padded row, N for padding), ``dest``
    ((N, k) int32: the padded row of each pair) and ``groups`` ((nm + 1,)
    int32: each tile's expert, then the live tile count; dead tiles carry
    the last live tile's expert).  Within an expert, pairs keep their
    (row, slot) order."""
    N, k = eidx.shape
    rows = N * k
    nm = grouped_tiles(rows, n_groups, bm)
    flat = eidx.reshape(rows).astype(jnp.int32)
    onehot = jax.nn.one_hot(flat, n_groups, dtype=jnp.int32)
    rank = jnp.take_along_axis(jnp.cumsum(onehot, axis=0), flat[:, None],
                               axis=1)[:, 0] - 1
    counts = jnp.sum(onehot, axis=0)
    padded = -(-counts // bm) * bm
    ends = jnp.cumsum(padded)
    row = (ends - padded)[flat] + rank
    src = jnp.full((nm * bm,), N, jnp.int32).at[row].set(
        jnp.arange(rows, dtype=jnp.int32) // k)
    n_live = ends[-1] // bm
    tiles = jnp.arange(nm, dtype=jnp.int32)
    expert = jnp.searchsorted(ends, tiles * bm, side="right").astype(jnp.int32)
    expert = jnp.where(tiles < n_live, expert, expert[n_live - 1])
    groups = jnp.concatenate([expert, n_live[None].astype(jnp.int32)])
    return src, row.reshape(N, k), groups


@functools.partial(
    jax.jit,
    static_argnames=("T", "v_th", "tau", "bm", "n_out", "fuse_lif",
                     "interpret"),
)
def _bsr_call_grouped(a_packed, plan, groups, T, v_th, tau, bm, n_out,
                      fuse_lif, interpret):
    """(nm * bm, K) expert-grouped packed spikes against an expert-stacked
    plan -> (nm * bm, n_out) packed spikes (``fuse_lif``) or float32 rates.
    Named with the `_bsr_call` prefix, by which traces count the kernel."""
    global BSR_TRACE_COUNT
    BSR_TRACE_COUNT += 1  # trace-time side effect, by design (see _bsr_call)
    K = a_packed.shape[1]
    if K > plan.k_padded:
        raise ValueError(f"spike width {K} exceeds plan K {plan.k_padded}")
    ap = (jnp.pad(a_packed, [(0, 0), (0, plan.k_padded - K)])
          if plan.k_padded > K else a_packed)
    act = block_activity_map(ap, bm, plan.bk).astype(jnp.int32)
    c = _k.ftp_spmm_bsr_grouped(
        ap, plan.payload, plan.kidx, plan.vidx, plan.cnt, act, groups,
        plan.n_padded, T, v_th, tau, bm=bm, fuse_lif=fuse_lif,
        interpret=interpret,
    )
    return c[:, :n_out]


def _dual_sparse_once(
    a_packed: np.ndarray,
    b: np.ndarray,
    T: int,
    v_th: float = DEFAULT_VTH,
    tau: float = DEFAULT_TAU,
    *,
    bm=_k.BM,
    bk=_k.BK,
    bn=_k.BN,
    fuse_lif: bool = True,
    interpret: bool | None = None,
    adaptive: bool = False,
    min_spikes: int = 1,
):
    """End-to-end dual-sparse LoAS layer: plan construction + BSR kernel.

    Convenience entry (numpy/dense weights in, jax out) for tests, examples
    and offline experiments — it builds the `WeightJoinPlan` per call.  A
    real serving path builds plans once at model load
    (`snn_layers.attach_join_plans` / `models.layers.attach_spiking_ffn_plans`)
    and reuses them across requests.
    """
    M, K = a_packed.shape
    N = b.shape[1]
    bm_, bk_, bn_ = _pick_blocks(M, K, N, bm, bk, bn)
    plan = build_weight_plan(np.asarray(b), bk=bk_, bn=bn_)
    return _bsr(
        jnp.asarray(a_packed), plan, T, v_th, tau,
        bm=bm_, n_out=N, fuse_lif=fuse_lif, interpret=interpret,
        adaptive=adaptive, min_spikes=min_spikes,
    )


# ---------------------------------------------------------------------------
# The policy front door.
# ---------------------------------------------------------------------------

def dispatch(
    a,
    weights_or_plan,
    policy,
    T: int,
    *,
    fuse_lif: bool = False,
    v_th: float = DEFAULT_VTH,
    tau: float = DEFAULT_TAU,
    n_out: int | None = None,
    bm: int | None = None,
    bk: int | None = None,
    bn: int | None = None,
    interpret: bool | None = None,
    groups=None,
):
    """Run one FTP layer under an `ExecutionPolicy` — the single public
    kernel entry point.

    ``a``: spike activations in the policy's ``spike_format`` — float:
    (T, M, K) f32 {0,1} planes; packed: (M, K) or batched (B, M, K) uint32
    words.  ``weights_or_plan``: a dense (K, N) weight matrix or a load-time
    `WeightJoinPlan` (requires ``weight_sparsity='dual_sparse'``).  A policy
    whose placement carries a mesh installs it for the call (sharded
    entries engage exactly as under the engine's serve-mesh scope);
    otherwise any ambient serve mesh applies.  Sharded entries exist for
    the plan path and the non-fused dense path (batched operands fold into
    rows first); the fused dense path has no sharded implementation and
    runs with single-device semantics even under a mesh.

    Returns (T, M[, N-batched], N) full sums without ``fuse_lif``; with it,
    (packed spike words | float spikes, membrane potentials) — the LoAS
    fused P-LIF layer in the policy's spike format.

    ``groups`` (`group_rows`): ``a`` is (nm * bm, K) packed rows sorted
    and padded by expert and the plan is stacked over experts; one grouped
    BSR call joins each row tile with its expert's plan.  Without
    ``fuse_lif`` it returns the (nm * bm, N) rate decode (the mean over T)
    instead of full sums; single device, full temporal walk only.

    Dual-sparse with RAW weights builds the plan per call (offline
    convenience); serving paths build plans once at load and pass them in.
    Policies with ``execution='pipelined'`` refuse the per-call path
    outright: dispatch must never force a host sync in the pipelined hot
    path, and plan building host-materializes the weights.
    """
    from repro.serve.policy import ExecutionPolicy  # lazy: serve sits above

    if not isinstance(policy, ExecutionPolicy):
        raise TypeError(
            f"dispatch needs an ExecutionPolicy, got {type(policy).__name__}"
            " — e.g. repro.serve.policy.PACKED_DENSE"
        )
    plan_like = isinstance(weights_or_plan, WeightJoinPlan)
    if plan_like and policy.weight_sparsity != "dual_sparse":
        raise ValueError(
            "got a WeightJoinPlan but policy.weight_sparsity="
            f"{policy.weight_sparsity!r}; use a dual_sparse policy "
            "(e.g. repro.serve.policy.PACKED_DUAL) or pass dense weights"
        )
    if (policy.execution == "pipelined"
            and policy.weight_sparsity == "dual_sparse" and not plan_like):
        # per-call plan building materializes the weights on the HOST —
        # a forced device sync in exactly the dispatch path the pipelined
        # executor keeps sync-free.  Loud error instead of a silent stall.
        raise ValueError(
            "execution='pipelined' forbids per-call plan building (it "
            "host-materializes the weights, forcing a device sync in the "
            "dispatch hot path); build the WeightJoinPlan once at load "
            "(join_plan.build_weight_plan / "
            "models.layers.attach_spiking_ffn_plans) and pass it in"
        )

    if policy.spike_format == "float":
        # Differentiable jnp path: (T, M, K) float {0,1} spikes.
        from repro.core.ftp import ftp_spmspm_unpacked
        from repro.core.lif import lif_forward

        o = ftp_spmspm_unpacked(a, weights_or_plan)
        if fuse_lif:
            return lif_forward(o, v_th=v_th, tau=tau)
        return o

    mesh = policy.mesh if policy.mesh is not None else get_serve_mesh()
    if groups is not None:
        if not plan_like or a.ndim != 2 or policy.temporal.enabled:
            raise ValueError("a grouped call takes (rows, K) packed spikes, "
                             "an expert-stacked plan and the full temporal "
                             "walk")
        if mesh is not None and mesh.size > 1:
            raise NotImplementedError("no grouped BSR entry under a mesh")
        return _bsr_call_grouped(
            a, weights_or_plan, groups, T, v_th, tau,
            a.shape[0] // (groups.shape[0] - 1),
            weights_or_plan.n_padded if n_out is None else n_out, fuse_lif,
            (not _on_tpu()) if interpret is None else interpret,
        )
    bm_ = _k.BM if bm is None else bm
    bk_ = _k.BK if bk is None else bk
    bn_ = _k.BN if bn is None else bn
    batched = a.ndim == 3
    # Temporal axis of the policy: the BSR kernels take the scored map
    # in-kernel (real skipped MXU work); the dense-weight kernels have no
    # in-kernel timestep walk, so lossy thresholds (min_spikes>1) realize as
    # value-level bit masking of the operand instead.  min_spikes=1 masking
    # is the identity (an all-silent plane has no bits), so the dense path
    # skips it outright.
    adaptive = policy.temporal.enabled
    min_spikes = policy.temporal.min_spikes if adaptive else 1
    with serve_mesh_scope(mesh):
        if plan_like:
            fn = _bsr_batched if batched else _bsr
            return fn(
                a, weights_or_plan, T, v_th, tau,
                bm=bm, n_out=n_out, fuse_lif=fuse_lif, interpret=interpret,
                adaptive=adaptive, min_spikes=min_spikes,
            )
        if adaptive and min_spikes > 1 and policy.weight_sparsity == "dense":
            a = mask_low_activity_timesteps(a, T, min_spikes)
        if policy.weight_sparsity == "dual_sparse":
            a2 = a.reshape(-1, a.shape[-1]) if batched else a
            out, u = _dual_sparse_once(
                a2, weights_or_plan, T, v_th, tau,
                bm=bm_, bk=bk_, bn=bn_, fuse_lif=fuse_lif,
                interpret=interpret,
                adaptive=adaptive, min_spikes=min_spikes,
            )
            if batched:
                B, M = a.shape[:2]
                u = u.reshape(B, M, -1)
                out = (out.reshape(B, M, -1) if fuse_lif
                       else out.reshape(T, B, M, -1))
            return out, u
        if fuse_lif:
            # no sharded fused dense entry exists: a mesh placement is
            # ignored here (single-device semantics, values unchanged)
            fn = _spmm_fused_batched if batched else _spmm_fused
            return fn(a, weights_or_plan, T, v_th, tau,
                      bm=bm_, bk=bk_, bn=bn_, interpret=interpret)
        if batched:
            # fold the batch into rows (exact — kernels are row-parallel)
            # so the mesh entry's row/column sharding applies to batches too
            B, M, K = a.shape
            out = _spmm_mesh(a.reshape(B * M, K), weights_or_plan, T,
                             mesh=mesh, bm=bm_, bk=bk_, bn=bn_,
                             interpret=interpret)
            return out.reshape(T, B, M, weights_or_plan.shape[1])
        return _spmm_mesh(a, weights_or_plan, T, mesh=mesh,
                          bm=bm_, bk=bk_, bn=bn_, interpret=interpret)


def dispatch_decode_window(
    a,
    weights_or_plan,
    policy,
    T: int,
    **kwargs,
):
    """Decode-window entry for speculative verify: ``a`` is a packed
    ``(B, S, K)`` operand — S = k+1 sequence positions of one speculative
    round per batch row, instead of the usual (B, M, K) row-batched layout.

    The window folds into the batched-rows BSR path (B*S rows), so the
    weight plan / dense weight tiles stream from HBM ONCE per round instead
    of once per token — the kernel-level reason one batched verify beats
    k+1 chained single-token dispatches.  Because every kernel under
    `dispatch` is row-parallel (each output row is an independent full-K
    contraction), each position's output is bitwise identical to its own
    (B, 1) dispatch — the property `policy.acceptance_lengths` relies on to
    keep the verified stream token-identical.

    Under ``temporal='adaptive'`` the activity score is pooled over the
    folded window (a plane skips only when silent across every position of
    every row), which preserves the min_spikes=1 bitwise guarantee
    per-position.
    """
    if getattr(a, "ndim", None) != 3:
        raise ValueError(
            "dispatch_decode_window takes a packed (B, S, K) window, got "
            f"shape {getattr(a, 'shape', None)} — use dispatch() for "
            "unbatched or float operands"
        )
    if policy.spike_format != "packed":
        raise ValueError(
            "decode windows are packed-spike shaped; policy has "
            f"spike_format={policy.spike_format!r}"
        )
    return dispatch(a, weights_or_plan, policy, T, **kwargs)


# ---------------------------------------------------------------------------
# Offline analysis helpers (not deprecated — no policy equivalent).
# ---------------------------------------------------------------------------

def build_block_join(
    a_packed: np.ndarray, b: np.ndarray, bm: int, bk: int, bn: int
):
    """Residual host-side join (offline analysis/debug): for every output
    tile (i, j), the list of k-blocks where A's block is active AND B's block
    is non-zero.  Vectorized (argsort over the joined mask — no Python loop
    over tiles); the SERVING path never calls this — it splits the join into
    `build_weight_plan` (load time) + the in-kernel activity skip.

    Returns (b_vals, kidx, vidx, cnt, jmax) in the fully-joined per-(i, j)
    layout.
    """
    M, K = a_packed.shape
    N = b.shape[1]
    payload, idx, bnz = build_block_csr(np.asarray(b), bk, bn)
    a_act = np.asarray(block_activity_map(jnp.asarray(a_packed), bm, bk))
    nm, nkb = a_act.shape
    nnb = N // bn

    # joined[i, j, kb] = a_act[i, kb] & bnz[kb, j]
    joined = a_act[:, None, :] & bnz.T[None, :, :]  # (nm, nnb, nkb)
    cnt = joined.sum(axis=2).astype(np.int32)
    jmax = max(1, int(cnt.max()))
    # Stable argsort over ~joined floats survivors to the front, in ascending
    # k order per (i, j) tile — the vectorized form of the old double loop.
    order = np.argsort(~joined, axis=2, kind="stable")[..., :jmax]
    live = np.arange(jmax)[None, None, :] < cnt[..., None]
    kidx = np.where(live, order, 0).astype(np.int32)
    vidx = np.where(
        live, idx[kidx, np.arange(nnb)[None, :, None]], 0
    ).astype(np.int32)
    return payload, kidx, vidx, cnt, jmax
