"""Pallas TPU kernels for the FTP dataflow (DESIGN.md §3).

Three kernels (and their grouped form, kernel 4 below):

* ``ftp_spmm``            — packed spikes x dense weights -> (T, M, N) sums.
* ``ftp_spmm_fused_lif``  — same, with the P-LIF epilogue fused in VMEM;
                            emits PACKED output spike words (uint32) + final
                            membrane potentials.  The (T, bm, bn) full-sum
                            tile never leaves VMEM — the TPU realization of
                            the paper's IP output reuse + P-LIF "one shot".
* ``ftp_spmm_bsr``        — dual-sparse: block-CSR weights joined with the
                            spike block-activity map (block-level inner join,
                            DESIGN.md D1).  The weight side of the join is a
                            STATIC load-time plan (kernels/join_plan.py)
                            driving the grid via scalar-prefetch index maps;
                            the spike side is a per-request device-computed
                            activity map consumed in-kernel with @pl.when —
                            no host join, no recompile across requests.
                            With ``tmap`` (timestep-activity map) the same
                            machinery gates a third axis: per-timestep bit
                            planes whose total spike score is below the
                            policy threshold skip their MXU work entirely
                            (adaptive temporal sparsity; value change only,
                            zero retrace).

Dataflow notes (why this is FTP):
  The grid is (m, n, k) — the inner-product loop nest.  Inside one grid step
  the T bit-planes of the packed spike block are unpacked in-register (VPU
  shift+mask) and contracted against the SAME weight tile resident in VMEM,
  by folding T into the row dimension of a single (T*bm, bk) x (bk, bn) MXU
  call.  The weight tile is therefore fetched from HBM exactly once per
  (m, n, k) block regardless of T — the paper's `parallel-for t` (goal 1) —
  and the accumulator carries (T*bm, bn) in VMEM across k steps (goal 2: no
  temporal partial sums to memory).  T never appears in the grid (goal 3: no
  T x latency).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.lif import DEFAULT_TAU, DEFAULT_VTH

# Default MXU-aligned tile sizes (v5e MXU is 128x128; 8-sublane f32 tiles).
BM, BK, BN = 128, 128, 128


def _bit_plane(a_block: jax.Array, t: int, dtype) -> jax.Array:
    """Timestep ``t`` of (bm, bk) uint32 spike words as {0,1} ``dtype``.

    The masked bit goes through int32 on its way to float: Mosaic has no
    uint32 -> float32 cast (the interpreter accepts one, so only a compile
    for the chip catches it).  The value is 0 or 1, so the detour is exact.
    """
    bit = (a_block >> jnp.uint32(t)) & jnp.uint32(1)
    return bit.astype(jnp.int32).astype(dtype)


def _unpack_fold(a_block: jax.Array, T: int, acc_dtype) -> jax.Array:
    """(bm, bk) uint32 -> (T*bm, bk) {0,1} bit-planes, T-major.

    VPU work: one shift+and per timestep; the fold lets a single MXU call
    process all T planes with one weight tile (the `parallel-for t`).
    """
    planes = [_bit_plane(a_block, t, acc_dtype) for t in range(T)]
    return jnp.concatenate(planes, axis=0)  # (T*bm, bk)


def _lif_epilogue(acc, T: int, v_th: float, tau: float):
    """LIF over the (T*bm, bn) accumulator; returns packed spikes + final U."""
    bm = acc.shape[0] // T
    u = jnp.zeros((bm, acc.shape[1]), dtype=acc.dtype)
    packed = jnp.zeros((bm, acc.shape[1]), dtype=jnp.uint32)
    for t in range(T):
        x = acc[t * bm : (t + 1) * bm] + u
        c = x > v_th
        u = tau * x * (1.0 - c.astype(acc.dtype))
        packed = packed | (c.astype(jnp.uint32) << t)
    return packed, u


# ---------------------------------------------------------------------------
# Kernel 1: dense-weight FTP spMspM.
# ---------------------------------------------------------------------------

def _ftp_spmm_kernel(a_ref, b_ref, o_ref, acc_ref, *, T, nk):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = _unpack_fold(a_ref[...], T, jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    acc_ref[...] += jnp.dot(a, b, preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _():
        o_ref[...] = acc_ref[...].reshape(o_ref.shape)


def ftp_spmm(
    a_packed: jax.Array,
    b: jax.Array,
    T: int,
    *,
    bm: int = BM,
    bk: int = BK,
    bn: int = BN,
    interpret: bool = False,
) -> jax.Array:
    """(M, K) uint32 x (K, N) -> (T, M, N) f32.  Shapes must be block-aligned
    (the ops.py wrapper pads)."""
    M, K = a_packed.shape
    K2, N = b.shape
    assert K == K2 and M % bm == 0 and K % bk == 0 and N % bn == 0
    nm, nn, nk = M // bm, N // bn, K // bk
    grid = (nm, nn, nk)
    return pl.pallas_call(
        functools.partial(_ftp_spmm_kernel, T=T, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((T, bm, bn), lambda i, j, k: (0, i, j)),
        out_shape=jax.ShapeDtypeStruct((T, M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((T * bm, bn), jnp.float32)],
        interpret=interpret,
    )(a_packed, b)


# ---------------------------------------------------------------------------
# Kernel 2: fused P-LIF epilogue -> packed output spikes.
# ---------------------------------------------------------------------------

def _ftp_spmm_lif_kernel(
    a_ref, b_ref, c_ref, u_ref, acc_ref, *, T, nk, v_th, tau
):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = _unpack_fold(a_ref[...], T, jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    acc_ref[...] += jnp.dot(a, b, preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _():
        packed, u = _lif_epilogue(acc_ref[...], T, v_th, tau)
        c_ref[...] = packed
        u_ref[...] = u.astype(u_ref.dtype)


def ftp_spmm_fused_lif(
    a_packed: jax.Array,
    b: jax.Array,
    T: int,
    v_th: float = DEFAULT_VTH,
    tau: float = DEFAULT_TAU,
    *,
    bm: int = BM,
    bk: int = BK,
    bn: int = BN,
    interpret: bool = False,
):
    """(M, K) uint32 x (K, N) -> ((M, N) uint32 packed spikes, (M, N) f32 U).

    Output traffic is T bits + 32 bits per neuron instead of T x f32: the
    full-sum tensor O is never materialized in HBM (paper goal 2, fused
    P-LIF)."""
    M, K = a_packed.shape
    K2, N = b.shape
    assert K == K2 and M % bm == 0 and K % bk == 0 and N % bn == 0
    nm, nn, nk = M // bm, N // bn, K // bk
    return pl.pallas_call(
        functools.partial(
            _ftp_spmm_lif_kernel, T=T, nk=nk, v_th=v_th, tau=tau
        ),
        grid=(nm, nn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, N), jnp.uint32),
            jax.ShapeDtypeStruct((M, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((T * bm, bn), jnp.float32)],
        interpret=interpret,
    )(a_packed, b)


# ---------------------------------------------------------------------------
# Kernel 3: dual-sparse block-CSR weights + block-level inner join.
#
# The join is split by lifetime (kernels/join_plan.py):
#   * weight side (static, per model load): the grid's jj axis walks ONLY the
#     weight-non-zero k-blocks of output column j, through the prefetched
#     kidx/vidx/cnt join lists — zero k-blocks never enter the grid;
#   * spike side (dynamic, per request): a device-computed block-activity map
#     rides in as a scalar-prefetch (SMEM) operand and spike-silent blocks
#     are skipped in-kernel with @pl.when — no host round-trip, no per-call
#     join construction, and a change in spike activity is a pure value
#     change (same shapes -> no retrace/recompile).
# ---------------------------------------------------------------------------

def _ftp_bsr_kernel(
    kidx_ref, vidx_ref, cnt_ref, act_ref,  # scalar-prefetch operands
    a_ref, bv_ref, c_ref, u_ref, acc_ref,
    *, T, jmax, v_th, tau, fuse_lif,
):
    i = pl.program_id(0)
    j = pl.program_id(1)
    jj = pl.program_id(2)

    @pl.when(jj == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Block-level inner join: jj runs over the STATIC weight-non-zero k-block
    # list of column j (tail slots masked by cnt); the DYNAMIC spike side is
    # the device-computed activity map — A-silent blocks contribute nothing
    # and skip the MXU entirely.
    kb = kidx_ref[j, jj]

    @pl.when(jnp.logical_and(jj < cnt_ref[j], act_ref[i, kb] > 0))
    def _():
        a = _unpack_fold(a_ref[...], T, jnp.float32)
        b = bv_ref[0].astype(jnp.float32)
        acc_ref[...] += jnp.dot(a, b, preferred_element_type=jnp.float32)

    @pl.when(jj == jmax - 1)
    def _():
        if fuse_lif:
            packed, u = _lif_epilogue(acc_ref[...], T, v_th, tau)
            c_ref[...] = packed
            u_ref[...] = u.astype(u_ref.dtype)
        else:
            c_ref[...] = acc_ref[...].reshape(c_ref.shape)
            # no LIF ran, so there are no membrane potentials; zero-fill
            # rather than leave the output buffer uninitialized
            u_ref[...] = jnp.zeros_like(u_ref)


def _ftp_bsr_adaptive_kernel(
    kidx_ref, vidx_ref, cnt_ref, act_ref, tmap_ref,  # scalar-prefetch
    a_ref, bv_ref, c_ref, u_ref, acc_ref,
    *, T, jmax, v_th, tau, fuse_lif,
):
    """Triple-sparse body: weight join x spike activity x TIMESTEP activity.

    Identical to `_ftp_bsr_kernel` except the folded single (T*bm, bk) MXU
    call is split into T per-plane (bm, bk) calls, each gated by the
    scalar-prefetched timestep-activity map ``tmap`` — the temporal third of
    the join.  The walk over timesteps is unrolled at trace time and the
    grid stays (nm, nnb, jmax): a change in which timesteps are silent is a
    pure value change of ``tmap`` (same shapes -> no retrace), and a skipped
    plane skips its MXU work entirely.  The LIF epilogue still runs over ALL
    T timesteps — a silent input plane contributes exactly zero current, but
    the membrane recurrence (leak, threshold, carried potential) must see it,
    which is what keeps min_spikes=1 skipping bitwise.
    """
    i = pl.program_id(0)
    j = pl.program_id(1)
    jj = pl.program_id(2)

    @pl.when(jj == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kb = kidx_ref[j, jj]
    bm = a_ref.shape[0]

    @pl.when(jnp.logical_and(jj < cnt_ref[j], act_ref[i, kb] > 0))
    def _():
        a_word = a_ref[...]
        b = bv_ref[0].astype(jnp.float32)
        for t in range(T):

            @pl.when(tmap_ref[t] > 0)
            def _(t=t):
                plane = _bit_plane(a_word, t, jnp.float32)
                acc_ref[t * bm : (t + 1) * bm, :] += jnp.dot(
                    plane, b, preferred_element_type=jnp.float32
                )

    @pl.when(jj == jmax - 1)
    def _():
        if fuse_lif:
            packed, u = _lif_epilogue(acc_ref[...], T, v_th, tau)
            c_ref[...] = packed
            u_ref[...] = u.astype(u_ref.dtype)
        else:
            c_ref[...] = acc_ref[...].reshape(c_ref.shape)
            u_ref[...] = jnp.zeros_like(u_ref)


def ftp_spmm_bsr(
    a_packed: jax.Array,
    b_vals: jax.Array,
    kidx: jax.Array,
    vidx: jax.Array,
    cnt: jax.Array,
    act: jax.Array,
    N: int,
    T: int,
    v_th: float = DEFAULT_VTH,
    tau: float = DEFAULT_TAU,
    *,
    tmap: jax.Array | None = None,
    bm: int = BM,
    fuse_lif: bool = True,
    interpret: bool = False,
):
    """Dual-sparse FTP spMspM over a load-time weight join plan.

    a_packed: (M, K) uint32 packed spikes (dense layout; silent blocks are
              skipped in-kernel via ``act``).
    b_vals:   (nnzb, bk, bn) gathered non-zero weight blocks (block-CSR
              payload; see join_plan.build_weight_plan).
    kidx:     (nnb, jmax) int32 — k-block index into A per join slot of
              output column block j (weight-side static join list).
    vidx:     (nnb, jmax) int32 — block index into b_vals per join slot.
    cnt:      (nnb,) int32 — live join slots per column block.
    act:      (nm, nkb) int32 — device-computed spike block-activity map
              (>0 where the (bm, bk) spike block has any non-silent neuron).
    tmap:     optional (T,) int32 device-computed timestep-activity map
              (>0 where timestep plane t clears the policy's min_spikes
              score).  When given, the adaptive triple-sparse kernel runs
              and inactive planes skip their MXU work; when None, the folded
              single-MXU-call kernel runs (temporal='full').
    """
    M, K = a_packed.shape
    nnzb, bk, bn = b_vals.shape
    nnb, jmax = kidx.shape
    nm, nkb = act.shape
    assert M % bm == 0 and K == nkb * bk and N == nnb * bn and nm == M // bm

    adaptive = tmap is not None
    if adaptive:
        assert tmap.shape == (T,), (tmap.shape, T)
        kernel = _ftp_bsr_adaptive_kernel
        prefetch = (kidx, vidx, cnt, act, tmap)
    else:
        kernel = _ftp_bsr_kernel
        prefetch = (kidx, vidx, cnt, act)

    # index maps take (grid ids..., *scalar-prefetch refs); written with *_
    # so the same lambdas serve both prefetch arities (4 or 5 operands)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(nm, nnb, jmax),
        in_specs=[
            pl.BlockSpec(
                (bm, bk),
                lambda i, j, jj, kidx, *_: (i, kidx[j, jj]),
            ),
            pl.BlockSpec(
                (1, bk, bn),
                lambda i, j, jj, kidx, vidx, *_: (vidx[j, jj], 0, 0),
            ),
        ],
        out_specs=[
            pl.BlockSpec(
                (bm, bn) if fuse_lif else (T, bm, bn),
                (lambda i, j, jj, *_: (i, j))
                if fuse_lif
                else (lambda i, j, jj, *_: (0, i, j)),
            ),
            pl.BlockSpec((bm, bn), lambda i, j, jj, *_: (i, j)),
        ],
        scratch_shapes=[pltpu.VMEM((T * bm, bn), jnp.float32)],
    )
    out_shape = [
        jax.ShapeDtypeStruct(
            (M, N) if fuse_lif else (T, M, N),
            jnp.uint32 if fuse_lif else jnp.float32,
        ),
        jax.ShapeDtypeStruct((M, N), jnp.float32),
    ]
    c, u = pl.pallas_call(
        functools.partial(
            kernel,
            T=T,
            jmax=jmax,
            v_th=v_th,
            tau=tau,
            fuse_lif=fuse_lif,
        ),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(*prefetch, a_packed, b_vals)
    return c, u


# ---------------------------------------------------------------------------
# Kernel 4: grouped dual-sparse BSR — one call over all experts of a layer.
#
# The rows arrive sorted by expert and padded per expert to the row tile
# (`ops.group_rows`), so every (bm, .) row tile belongs to one expert.  The
# tile's expert rides in as one more scalar-prefetch operand (``groups``:
# the expert of each tile, then the live tile count) and selects that
# expert's join lists and payload blocks; the weight join, the spike join
# and the fused epilogue are the dense BSR kernel's.  Steps of tiles past
# the last live one, and join slots past a column's count, repeat the
# previous step's block indices: no DMA is issued for them and the body
# does no MXU work, so a call reads the kept blocks of the experts that
# hold rows and nothing else.  The join lists are flattened to 1-D so the
# scalar memory holds them unpadded.
# ---------------------------------------------------------------------------

def grouped_slot(i, j, jj, cnt, groups, *, nm, nnb, jmax):
    """(row tile, expert, flat join slot) whose blocks grid step
    (i, j, jj) fetches.  Live steps: their own tile and column, the join
    slot clamped to the column's last live one.  Steps of dead tiles
    (i >= the live count): the last live step's, so nothing is fetched."""
    n_live = groups[nm]
    live = i < n_live
    it = jnp.where(live, i, n_live - 1)
    jt = jnp.where(live, j, nnb - 1)
    e = groups[it]
    last = jnp.maximum(cnt[e * nnb + jt] - 1, 0)
    st = jnp.where(live, jnp.minimum(jj, last), last)
    return it, jt, e, (e * nnb + jt) * jmax + st


def rate_epilogue(acc, T: int):
    """(T*bm, bn) full sums -> (bm, bn) firing-rate decode: the mean over
    the T timestep planes, summed in timestep order."""
    bm = acc.shape[0] // T
    s = acc[0:bm]
    for t in range(1, T):
        s = s + acc[t * bm:(t + 1) * bm]
    return s / T


def _ftp_bsr_grouped_kernel(
    kidx_ref, vidx_ref, cnt_ref, act_ref, grp_ref,   # scalar-prefetch
    a_ref, bv_ref, c_ref, acc_ref,
    *, T, nm, nnb, nkb, jmax, v_th, tau, fuse_lif,
):
    i = pl.program_id(0)
    j = pl.program_id(1)
    jj = pl.program_id(2)
    live = i < grp_ref[nm]
    e = grp_ref[i]
    col = e * nnb + j

    @pl.when(jnp.logical_and(live, jj == 0))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kb = kidx_ref[col * jmax + jj]
    joined = jnp.logical_and(jj < cnt_ref[col], act_ref[i * nkb + kb] > 0)

    @pl.when(jnp.logical_and(live, joined))
    def _():
        a = _unpack_fold(a_ref[...], T, jnp.float32)
        b = bv_ref[0, 0].astype(jnp.float32)
        acc_ref[...] += jnp.dot(a, b, preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(live, jj == jmax - 1))
    def _():
        if fuse_lif:
            packed, _ = _lif_epilogue(acc_ref[...], T, v_th, tau)
            c_ref[...] = packed
        else:
            c_ref[...] = rate_epilogue(acc_ref[...], T)


def ftp_spmm_bsr_grouped(
    a_packed: jax.Array,
    b_vals: jax.Array,
    kidx: jax.Array,
    vidx: jax.Array,
    cnt: jax.Array,
    act: jax.Array,
    groups: jax.Array,
    N: int,
    T: int,
    v_th: float = DEFAULT_VTH,
    tau: float = DEFAULT_TAU,
    *,
    bm: int = BM,
    fuse_lif: bool = True,
    interpret: bool = False,
):
    """Dual-sparse FTP spMspM of expert-grouped rows over per-expert plans.

    a_packed: (nm * bm, K) uint32 packed spikes, rows sorted by expert and
              padded per expert to ``bm`` (`ops.group_rows`).
    b_vals:   (E, nnzb, bk, bn) each expert's block-CSR payload.
    kidx, vidx: (E, nnb, jmax) int32 per-expert join lists; cnt: (E, nnb).
    act:      (nm, nkb) int32 spike block-activity map.
    groups:   (nm + 1,) int32 — the expert of each row tile, then the live
              tile count (tiles past it are padding and skipped).
    Returns (nm * bm, N): packed uint32 spikes with ``fuse_lif`` (the LIF
    epilogue), else float32 rates (the mean of the T full sums).  Rows of
    dead tiles are left unwritten.
    """
    M, K = a_packed.shape
    E, nnzb, bk, bn = b_vals.shape
    _, nnb, jmax = kidx.shape
    nm, nkb = act.shape
    assert M == nm * bm and K == nkb * bk and N == nnb * bn
    assert groups.shape == (nm + 1,)
    geo = dict(nm=nm, nnb=nnb, jmax=jmax)

    def a_map(i, j, jj, kidx, vidx, cnt, act, grp):
        it, _, _, slot = grouped_slot(i, j, jj, cnt, grp, **geo)
        return it, kidx[slot]

    def b_map(i, j, jj, kidx, vidx, cnt, act, grp):
        _, _, e, slot = grouped_slot(i, j, jj, cnt, grp, **geo)
        return e, vidx[slot], 0, 0

    def c_map(i, j, jj, kidx, vidx, cnt, act, grp):
        it, jt, _, _ = grouped_slot(i, j, jj, cnt, grp, **geo)
        return it, jt

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(nm, nnb, jmax),
        in_specs=[pl.BlockSpec((bm, bk), a_map),
                  pl.BlockSpec((1, 1, bk, bn), b_map)],
        out_specs=pl.BlockSpec((bm, bn), c_map),
        scratch_shapes=[pltpu.VMEM((T * bm, bn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(
            _ftp_bsr_grouped_kernel, T=T, nm=nm, nnb=nnb, nkb=nkb,
            jmax=jmax, v_th=v_th, tau=tau, fuse_lif=fuse_lif,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (M, N), jnp.uint32 if fuse_lif else jnp.float32),
        interpret=interpret,
    )(kidx.reshape(-1), vidx.reshape(-1), cnt.reshape(-1), act.reshape(-1),
      groups, a_packed, b_vals)
