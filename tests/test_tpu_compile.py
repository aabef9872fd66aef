"""Ahead-of-time compiles of the serving path's Pallas kernels for a TPU v5e.

The rest of the suite runs every kernel through the Pallas interpreter,
which accepts programs the chip's kernel compiler (Mosaic) refuses — a
uint32 -> float32 cast in the spike unpack was one.  These tests hand the
kernels to the real compiler for a described, not attached, v5e chip at
the widths the serving path runs them:

* llama3.2-1B's spiking FFN (d_model 2048, d_ff 8192, T=4, weight density
  0.3): the hidden GEMM with the fused LIF epilogue and the output GEMM
  that returns full sums, at decode rows (4 and 12 — not a multiple of the
  8-row tile) and prefill rows (512 and 4096; the scalar-prefetched
  activity map grows with the rows), for the full and adaptive temporal
  bodies of the dual-sparse BSR kernel;
* the dense-weight kernels (`ftp_spmm`, `ftp_spmm_fused_lif`) at the same
  widths;
* the paper's Table II T-HFF layer (T=4, M=784, N=K=3072);
* the grouped BSR kernel of the spiking experts at Mellum2's widths (64
  experts, 2304 x 896 and back), for the routed rows of a one- and a
  four-token decode and of 2048- and 16384-token prefills (the last,
  with its 1087 row tiles, the largest scalar-prefetched activity map);
* the BSR kernel under a data-only mesh of two described chips, where the
  whole plan is replicated and Mosaic, which cannot partition a kernel
  itself, must get it per data shard.

Nothing runs: a compile that passes says nothing of results or speed.
The topology is described inside a module fixture and never at import,
because only one process at a time may load the TPU library and every
test worker imports every test file.
"""
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.kernels import ops
from repro.kernels.join_plan import WeightJoinPlan, pick_plan_blocks
from repro.serve.policy import PACKED_DENSE, PACKED_DUAL, PACKED_DUAL_ADAPTIVE
from repro.sim.workloads import TABLE_II_LAYERS

T = 4
D_MODEL, D_FF = 2048, 8192
DENSITY = 0.3


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # the TPU compiler otherwise writes its logs under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without the chip; keep these compiles out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topo
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e):
    return SingleDeviceSharding(v5e.devices[0])


def _spikes(M, K, sharding):
    return jax.ShapeDtypeStruct((M, K), jnp.uint32, sharding=sharding)


def _plan(K, N, density, sharding):
    """Shapes of the load-time join plan of one pruned (K, N) weight: the
    block grid of `pick_plan_blocks`, ``density`` of its blocks stored
    (bf16, the compute dtype), and join lists twice the mean live count
    per output column (capped at every k-block)."""
    bk, bn = pick_plan_blocks(K, N)
    nkb, nnb = K // bk, N // bn
    nnzb = math.ceil(density * nkb * nnb)
    jmax = min(nkb, 2 * math.ceil(density * nkb))
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    return WeightJoinPlan(
        payload=sds((nnzb, bk, bn), jnp.bfloat16),
        kidx=sds((nnb, jmax), jnp.int32),
        vidx=sds((nnb, jmax), jnp.int32),
        cnt=sds((nnb,), jnp.int32),
        bmap=sds((nkb, nnb), jnp.bool_),
    )


def _compile_text(fn, *args) -> str:
    """Compile for the described chip (raises what Mosaic raises) and
    return the optimized HLO, which must hold the kernel itself."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("adaptive", [False, True], ids=["full", "adaptive"])
@pytest.mark.parametrize("rows", [4, 12, 512, 4096])
@pytest.mark.parametrize(
    "K,N,fuse_lif",
    [(D_MODEL, D_FF, True), (D_FF, D_MODEL, False)],
    ids=["ffn_in_lif", "ffn_out_sums"],
)
def test_bsr_kernel_compiles_at_llama_ffn_widths(
    one_chip, K, N, fuse_lif, rows, adaptive
):
    policy = PACKED_DUAL_ADAPTIVE if adaptive else PACKED_DUAL

    def layer(a, plan):
        return ops.dispatch(
            a, plan, policy, T, fuse_lif=fuse_lif, n_out=N, interpret=False
        )

    _compile_text(
        layer, _spikes(rows, K, one_chip), _plan(K, N, DENSITY, one_chip)
    )


@pytest.mark.parametrize("fuse_lif", [True, False], ids=["lif", "sums"])
def test_dense_weight_kernels_compile_at_llama_ffn_widths(one_chip, fuse_lif):
    w = jax.ShapeDtypeStruct((D_MODEL, D_FF), jnp.bfloat16, sharding=one_chip)

    def layer(a, w):
        return ops.dispatch(
            a, w, PACKED_DENSE, T, fuse_lif=fuse_lif, interpret=False
        )

    _compile_text(layer, _spikes(12, D_MODEL, one_chip), w)


def test_bsr_kernel_compiles_at_paper_t_hff(one_chip):
    (t, M, N, K), *_, sp_b = TABLE_II_LAYERS["T-HFF"]

    def layer(a, plan):
        return ops.dispatch(
            a, plan, PACKED_DUAL, t, fuse_lif=True, n_out=N, interpret=False
        )

    _compile_text(
        layer, _spikes(M, K, one_chip), _plan(K, N, 1 - sp_b / 100, one_chip)
    )


@pytest.mark.parametrize(
    "K,N,fuse_lif",
    [(D_MODEL, D_FF, True), (D_FF, D_MODEL, False)],
    ids=["ffn_in_lif", "ffn_out_sums"],
)
def test_bsr_kernel_compiles_under_a_data_only_mesh(v5e, K, N, fuse_lif):
    mesh = Mesh(np.asarray(v5e.devices[:2]).reshape(2, 1), ("data", "model"))

    def layer(a, plan):
        return ops.dispatch(
            a, plan, PACKED_DUAL, T, fuse_lif=fuse_lif, n_out=N,
            interpret=False,
        )

    with ops.serve_mesh_scope(mesh):
        _compile_text(
            layer,
            _spikes(8, K, NamedSharding(mesh, P("data", None))),
            _plan(K, N, DENSITY, NamedSharding(mesh, P())),
        )


E_MOE, D_MOE, F_MOE, TOP_K = 64, 2304, 896, 8


def _expert_plan(K, N, sharding):
    """`_plan`'s shapes stacked over the experts."""
    p = _plan(K, N, DENSITY, sharding)
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        (E_MOE,) + a.shape, a.dtype, sharding=sharding), p)


@pytest.mark.parametrize("tokens", [1, 4, 2048, 16384])
@pytest.mark.parametrize(
    "K,N,fuse_lif",
    [(D_MOE, F_MOE, True), (F_MOE, D_MOE, False)],
    ids=["expert_in_lif", "expert_out_rates"],
)
def test_grouped_bsr_kernel_compiles_at_mellum2_expert_widths(
    one_chip, K, N, fuse_lif, tokens
):
    rows = tokens * TOP_K
    bm = ops.grouped_row_block(rows, E_MOE)
    nm = ops.grouped_tiles(rows, E_MOE, bm)

    def layer(a, plan, groups):
        return ops.dispatch(a, plan, PACKED_DUAL, T, fuse_lif=fuse_lif,
                            n_out=N, groups=groups, interpret=False)

    text = _compile_text(
        layer, _spikes(nm * bm, K, one_chip), _expert_plan(K, N, one_chip),
        jax.ShapeDtypeStruct((nm + 1,), jnp.int32, sharding=one_chip),
    )
    # the trace counts the kernel by this prefix (bench/trace_reduce.py)
    assert "%_bsr_call_grouped" in text
