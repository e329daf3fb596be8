"""vsmm — vector-sparse matmul Pallas TPU kernel (the paper's PE array on MXU).

Maps VSCNN's dataflow onto the TPU memory hierarchy:

  paper (ASIC)                          this kernel (TPU)
  ------------------------------------  -----------------------------------
  nonzero 1-D weight vectors in SRAM    nonzero (vk, vn) weight tiles in a
                                        balanced block-CSR; only those tiles
                                        are DMA'd HBM->VMEM by the grid
                                        pipeline (static skip: the zero
                                        tiles never cost cycles *or* FLOPs)
  per-vector index -> accumulator       scalar-prefetch ``idx`` in SMEM
                                        drives BlockSpec.index_map: the s-th
                                        issued vector of output strip j
                                        gathers activation K-tile idx[j,s]
                                        (K-tile-major activations, so the
                                        gather is a leading-axis block
                                        index)
  zero input vectors absent from SRAM   ``@pl.when(any(x!=0))`` runtime
                                        guard: an all-zero activation tile
                                        issues no MXU op (the TPU analogue
                                        of a skipped cycle; the DMA itself
                                        is pipelined and hidden)
  diagonal partial-sum accumulation     f32 VMEM accumulator revisited
                                        across the innermost sparse-K grid
                                        dimension (stays on-chip, one
                                        HBM write at s == S-1)
  dense/sparse in one datapath          the dense path is S == KB with
                                        idx[j, s] = s — same kernel

Grid: ``(NB, MB, S)`` — output strip j, activation row-block m, sparse step s
(innermost, so the output tile is revisited and accumulated in VMEM).

Activation layout: `build_vsmm_input` turns x (M, K) into the K-tile-major
(KB, M, vk).  A (bm, vk) block of the row-major (M, K) array has a minor
dim of vk = 32, which the TPU compiler refuses (the last two block dims
must divide by (8, 128) or equal the array's); a (1, bm, vk) block of the
K-tile-major array ends in (bm, vk) with vk the whole minor axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.vector_sparse import VectorSparse

__all__ = [
    "vsmm_pallas", "vsmm_kernel_cost", "vsmm_x_index_map", "vsmm_w_index_map",
    "vsmm_out_index_map", "vsmm_bias_index_map", "build_vsmm_input",
]


def _widen(x: jax.Array) -> jax.Array:
    """int8 -> f32 (exact).  The TPU compiler reshapes and compares packed
    int8 vectors only at tile-aligned shapes, so the conv kernels widen a
    tap window before they flatten or zero-test it."""
    return x.astype(jnp.float32) if x.dtype == jnp.int8 else x


def _mac_dot(x: jax.Array, w: jax.Array) -> jax.Array:
    """One sparse-step MAC on the MXU.

    int8 x int8 multiply-accumulates exactly in int32 (the MXU-native int8
    path; one step is at most 127*127*vk < 2^24, so the cast of the partial
    into the shared f32 accumulator is also exact).  An int8 weight against
    a `_widen`-ed activation runs on bf16 operands, which hold every int8
    value exactly, with f32 accumulation — the same exact sums.  f32
    inputs run at HIGHEST precision: the f32 path is held to an f32
    reference, not to a single bf16 pass.
    """
    if x.dtype == jnp.int8:
        return jnp.dot(x, w, preferred_element_type=jnp.int32).astype(
            jnp.float32)
    if w.dtype == jnp.int8:
        return jnp.dot(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    return jnp.dot(x, w, preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)


def _nonzero(x: jax.Array) -> jax.Array:
    """The input-side skip predicate: any nonzero element in the tile."""
    return jnp.any(_widen(x) != 0)


def _unpack_refs(refs, *, has_scale: bool, has_bias: bool,
                 has_residual: bool):
    """(scale, bias, residual, out, acc) refs after the kernel's inputs;
    the absent epilogue operands are None."""
    it = iter(refs)
    scale_ref = next(it) if has_scale else None
    bias_ref = next(it) if has_bias else None
    res_ref = next(it) if has_residual else None
    return scale_ref, bias_ref, res_ref, next(it), next(it)


def _epilogue(acc: jax.Array, scale_ref, bias_ref, res_ref, *,
              fuse_relu: bool) -> jax.Array:
    """The fused flush shared by every kernel: acc -> *scale -> +bias ->
    +residual -> max(0).  The ReLU zeros produced here are exactly the
    input vectors the *next* layer's input-side skip elides; the residual
    (ResNet shortcut) is added before the ReLU, so a whole block retires
    in-kernel with one HBM write.  Dequant (int8) comes first and is exact:
    the scales are powers of two, so FMA contraction with the bias add
    cannot change the result — parity with the structural jnp path is
    compiler-proof."""
    if scale_ref is not None:
        acc = acc * scale_ref[0].astype(jnp.float32)
    if bias_ref is not None:
        acc = acc + bias_ref[0].astype(jnp.float32)
    if res_ref is not None:
        acc = acc + res_ref[...].astype(jnp.float32)
    if fuse_relu:
        acc = jnp.maximum(acc, 0.0)
    return acc


def epilogue_operands(in_specs: list, args: list, *, nb: int, vn: int,
                      bias_map, scale, bias) -> None:
    """Append the per-cout ``scale``/``bias`` operands as (nb, 1, vn) with
    (1, 1, vn) blocks: the last two block dims then equal the array's, a
    layout the TPU compiler accepts (a (1, vn) block of (nb, vn) is not)."""
    for v in (scale, bias):
        if v is not None:
            in_specs.append(pl.BlockSpec((1, 1, vn), bias_map))
            args.append(v.reshape(nb, 1, vn))


def build_vsmm_input(x: jax.Array, vk: int) -> jax.Array:
    """x (M, K) -> the K-tile-major (K/vk, M, vk) activation layout the
    kernel gathers K-tiles from (one XLA transpose pass)."""
    m, k = x.shape
    assert k % vk == 0, (x.shape, vk)
    return x.reshape(m, k // vk, vk).transpose(1, 0, 2)


def vsmm_kernel_cost(
    *, m: int, nb: int, s_steps: int, vk: int, vn: int, in_itemsize: int = 4,
    w_itemsize: int = 4, out_itemsize: int = 4, residual_bytes: int = 0,
) -> pl.CostEstimate:
    """Kernel-side cost of the sparse matmul: every sparse step gathers a
    fresh (bm, vk) activation K-tile, the stored weight tiles stream once,
    the output strip is written once.  ``m`` is the kernel's (padded) row
    count — `core.accel_model.conv_layer_traffic` quotes the same formulas
    at the unpadded row count for the 1x1-conv route."""
    return pl.CostEstimate(
        flops=2 * m * nb * s_steps * vk * vn,
        bytes_accessed=(
            m * nb * s_steps * vk * in_itemsize
            + nb * s_steps * vk * vn * w_itemsize
            + m * nb * vn * out_itemsize
            + residual_bytes
        ),
        transcendentals=0,
    )


# --------------------------------------------------------------------------
# BlockSpec index maps (named factories — shared with `repro.analysis`).
# Grid order (j, mi, s) = (output strip, activation row-block, sparse step).
# --------------------------------------------------------------------------

def vsmm_x_index_map():
    """Activation K-tile gather: the paper's index system — the s-th issued
    vector of strip j reads activation K-tile idx[j, s] (the leading axis
    of the K-tile-major layout)."""
    def index_map(j, mi, s, idx):
        return (idx[j, s], mi, 0)
    return index_map


def vsmm_w_index_map():
    """The s-th stored weight vector of strip j."""
    def index_map(j, mi, s, idx):
        return (j, s, 0, 0)
    return index_map


def vsmm_out_index_map():
    """Output/residual (row-block, strip) tile."""
    def index_map(j, mi, s, idx):
        return (mi, j)
    return index_map


def vsmm_bias_index_map():
    """Strip j's (1, 1, vn) bias tile (excluded from the byte contract)."""
    def index_map(j, mi, s, idx):
        return (j, 0, 0)
    return index_map


def _kernel(idx_ref, x_ref, w_ref, *refs, fuse_relu: bool, has_scale: bool,
            has_bias: bool, has_residual: bool, skip_zero_inputs: bool):
    scale_ref, bias_ref, res_ref, o_ref, acc_ref = _unpack_refs(
        refs, has_scale=has_scale, has_bias=has_bias,
        has_residual=has_residual)
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0]

    def _mac():
        acc_ref[...] += _mac_dot(x, w_ref[0, 0])

    if skip_zero_inputs:
        # Paper's input-side zero-vector skip: an all-zero activation tile
        # (e.g. post-ReLU) issues no MXU work.  On the ASIC the vector is not
        # in SRAM at all; on TPU the DMA is pipelined/hidden and we predicate
        # off the compute, which is what costs cycles on the MXU.
        pl.when(_nonzero(x))(_mac)
    else:
        _mac()

    @pl.when(s == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = _epilogue(acc_ref[...], scale_ref, bias_ref, res_ref,
                               fuse_relu=fuse_relu).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("bm", "skip_zero_inputs", "fuse_relu", "interpret",
                     "out_dtype"),
)
def vsmm_pallas(
    x: jax.Array,
    vs: VectorSparse,
    *,
    bm: int = 256,
    bias: jax.Array | None = None,
    residual: jax.Array | None = None,
    scale: jax.Array | None = None,
    skip_zero_inputs: bool = True,
    fuse_relu: bool = False,
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    """x @ vector-sparse W (K, N) -> (M, N), with x given K-tile-major as
    (K/vk, M, vk) (`build_vsmm_input`).

    M must be a multiple of ``bm`` (the `ops.vsmm` wrapper pads).  FLOPs
    scale with vs.density — the zero weight vectors are structurally
    absent from the grid.  ``bias`` (N,), ``residual`` (M, N)
    and ``fuse_relu`` run the epilogue inside the kernel at flush time
    (f32 accumulator -> *scale -> +bias -> +residual -> max(0) -> cast).

    INT8: pass int8 ``x`` + int8 ``vs.vals`` + ``scale`` (N,) — the combined
    per-cout dequant scale (activation scale x weight scale).  Each step
    multiply-accumulates in int32 on the MXU and the f32 output materializes
    only at flush; the residual stays f32.
    """
    kb, m, vk_x = x.shape
    nb, s_steps, vk, vn = vs.vals.shape
    assert vk_x == vk and kb * vk == vs.shape[0], (x.shape, vs.shape, vk)
    assert m % bm == 0, (m, bm)
    out_dtype = out_dtype or (jnp.float32 if x.dtype == jnp.int8 else x.dtype)
    has_scale = scale is not None
    has_bias = bias is not None
    has_residual = residual is not None

    in_specs = [
        pl.BlockSpec((1, bm, vk), vsmm_x_index_map()),
        pl.BlockSpec((1, 1, vk, vn), vsmm_w_index_map()),
    ]
    args = [vs.idx, x, vs.vals]
    epilogue_operands(in_specs, args, nb=nb, vn=vn,
                      bias_map=vsmm_bias_index_map(), scale=scale, bias=bias)
    if has_residual:
        assert residual.shape == (m, nb * vn), (residual.shape, m, nb * vn)
        in_specs.append(pl.BlockSpec((bm, vn), vsmm_out_index_map()))
        args.append(residual)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb, m // bm, s_steps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, vn), vsmm_out_index_map()),
        scratch_shapes=[pltpu.VMEM((bm, vn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, fuse_relu=fuse_relu, has_scale=has_scale,
                          has_bias=has_bias, has_residual=has_residual,
                          skip_zero_inputs=skip_zero_inputs),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, nb * vn), out_dtype),
        interpret=interpret,
        cost_estimate=vsmm_kernel_cost(
            m=m, nb=nb, s_steps=s_steps, vk=vk, vn=vn,
            in_itemsize=x.dtype.itemsize,
            w_itemsize=vs.vals.dtype.itemsize,
            out_itemsize=jnp.dtype(out_dtype).itemsize,
            residual_bytes=(residual.size * residual.dtype.itemsize
                            if has_residual else 0),
        ),
    )(*args)
