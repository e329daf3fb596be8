"""vsconv — direct KxK vector-sparse convolution Pallas TPU kernels.

The paper decomposes a conv into kernel *columns* (WA/WB/WC in Fig. 6) and
skips all-zero columns and all-zero input column vectors.  The TPU analogue
decomposes an arbitrary ``kh x kw`` / stride-``s`` conv into kernel *taps*
x input-channel tiles:

    conv(x, w)[i, j] = sum_{ky, kx} x[s*i + ky - pt, s*j + kx - pl] @ w[ky, kx]
                     = sum over K-tiles t = (ky*kw + kx, cin-tile) of
                       gather(x, t)[i, j] @ w_tile[t]          (kh*kw*CB matmuls)

A "weight vector" here is one (vk cin, vn cout) tile of one tap — pruned tiles
are structurally absent from the balanced block-CSR, so their matmuls never
enter the grid (the paper's weight-side skip).  An all-zero shifted-input row
block is skipped at runtime with ``@pl.when`` (the input-side skip).

Input layouts — two implementations of the same math
----------------------------------------------------

**Halo (default, `vsconv_halo_pallas`)** reads the raw SAME-padded NHWC
input *directly*.  `build_halo_input` pads it and splits it into its
``stride x stride`` phases (space-to-depth) in one XLA pass:

  XH (N, CB, stride**2, rows, bW, vk)
  XH[n, c, py*stride + px, r, q] = pad(x)[n, stride*r + py, stride*q + px, c]

with rows = Hout + ((kh-1)*dilation) // stride (stride 1: one phase,
rows = Hout + kh - 1).  The cin-tile axis sits ahead of the spatial axes
and vk is the whole minor axis, so every block ends in (rows-window, bW,
vk) — a layout the TPU compiler accepts.  The BlockSpec carves, per output
row-block of ``bh`` rows, an overlapping *halo block* of
``bh + ((kh-1)*dilation) // stride`` phase rows of every phase
(`pl.Element` element-offset indexing), and the tap ``(ky, kx)`` is
resolved *inside* the kernel: output pixel ``(i, j)`` reads phase
``((ky*dilation) % stride, (kx*dilation) % stride)`` at row
``i + (ky*dilation) // stride`` and column ``j + (kx*dilation) // stride``
— contiguous windows, no strided subselect (which the TPU compiler lowers
to an unsupported gather).  Because the halo offsets depend only on the
row-block and the cin tile — not on the tap — consecutive sparse steps
over the same cin tile *revisit* the same block and Pallas skips the DMA:
with the stored tiles ordered cin-major (`core.vector_sparse.
conv_cin_major`, the order `models.graph.sparse_conv_from_dense` emits),
each cin tile's halo
is fetched once per (strip, row-block), so input HBM traffic is ~1x the
input plus the halo overlap — the paper's fetch-once-broadcast-everywhere
data movement story, realized as index arithmetic.

At tiny output heights (Hout < `RESIDENT_MAX_H`, e.g. ResNet layer4 on
32px inputs) the per-strip fetch floor min(S, CB) re-reads a halo window
that is essentially the whole padded input, so the ungrouped halo kernel
switches to a *resident* layout (`use_resident_halo`): one block holding
all CB cin tiles, offsets a function of the row-block only, the
(image, row-block) grid axis outermost — the input is DMA'd exactly once
per (image, row-block) and both tap and cin tile resolve in-kernel.

**Row-tap/phase stack (`vsconv_pallas`, oracle + fallback)** materializes
``build_row_tap_stack``:

  XT (N, kh*stride, Hout, bW, C)
  XT[:, ky*stride + phase, i, j'] = pad(x)[:, stride*i + ky, phase + stride*j']

Rows are pre-strided per tap row ``ky`` and the width axis pre-split into
its ``stride`` phases, so the whole tap select is BlockSpec index_map
arithmetic plus one contiguous width slice.  The price is data movement:
the stack is ``kh*stride`` output-sized planes written to HBM before every
conv (an extra XLA pass over every activation) and the kernel re-fetches
its plane on every sparse step.  It is kept as the bandwidth-dumb oracle
the halo path is tested against, and as a fallback layout.

Grouped, depthwise and dilated geometry
---------------------------------------

``dilation`` spaces the taps: the in-kernel tap resolve reads row
``ky*dilation`` / column ``kx*dilation`` (halo) or the dilated plane slice
(stack) and every extent formula uses the effective kernel size
``(k-1)*dilation + 1``.  ``groups`` shards the cin-tile axis: the weight
matrix is (kh*kw*Cin/groups, Cout) with output strips group-major, a
strip's stored tile ids are group-relative, and the input index_map adds
the group's base cin tile — so a grouped strip fetches only its own
group's channels (the per-group traffic accounting in
`halo_kernel_cost(cb=Cin/(groups*vk))`).  Depthwise (groups == Cin,
multiplier 1) degenerates to the per-channel tap kernels
(`vsconv_dw_halo_pallas` / `vsconv_dw_stack_pallas`): the weight is the
(kh*kw, C) tap matrix encoded vk=1 over vn-channel tiles, the MAC is
elementwise on the VPU, and the halo block — tap-independent AND strip ==
channel tile — is fetched exactly once per (strip, row-block).

`stack_kernel_cost` / `halo_kernel_cost` (and their `dw_*` depthwise
variants) are the shared HBM-traffic contract: the same formulas feed the
kernels' `pl.CostEstimate`, the `core.accel_model` DRAM traffic model, and
the benchmark gate that keeps the halo path's bytes strictly below the
stack path's.

Padding is XLA-"SAME" for the given stride (Hout = ceil(H/stride)); the
`ops.vsconv` wrapper computes it and pads Hout to a ``bh`` multiple.

Fused epilogue (both kernels): optional per-cout ``bias`` add, optional
``residual`` (ResNet shortcut) add, and ReLU run inside the kernel at flush
time (f32 accumulator -> +bias -> +residual -> max(0) -> cast).  Fusing the
ReLU means the *next* layer's input zeros — the vectors its input-side skip
elides — are produced on-chip for free, exactly the paper's post-ReLU
input-zero-vector story; fusing the residual means a whole ResNet basic
block retires with a single extra VMEM read, no extra HBM round-trip.

Grid (both): ``(NB, N * HB, S)`` — cout strip j, (image, row-block) m,
sparse step s.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.sparse_ops import same_pads
from repro.core.vector_sparse import VectorSparse
from repro.kernels.vsmm import (
    _epilogue, _mac_dot, _nonzero, _unpack_refs, _widen, epilogue_operands,
)

__all__ = [
    "vsconv_pallas", "vsconv_halo_pallas", "vsconv_dw_halo_pallas",
    "vsconv_dw_stack_pallas", "build_row_tap_stack", "build_halo_input",
    "stack_kernel_cost", "halo_kernel_cost", "dw_halo_kernel_cost",
    "dw_stack_kernel_cost", "same_pads", "use_resident_halo",
    "RESIDENT_MAX_H", "halo_in_index_map", "resident_in_index_map",
    "dw_halo_in_index_map", "stack_in_index_map", "dw_stack_in_index_map",
    "conv_weight_index_map", "conv_out_index_map", "conv_bias_index_map",
    "halo_layout_dims", "stack_layout_dims", "halo_block_rows",
]


# --------------------------------------------------------------------------
# HBM traffic contract (shared by kernels, accel model, and benchmarks)
# --------------------------------------------------------------------------

def stack_kernel_cost(
    *, n: int, hop: int, w_out: int, bw: int, bh: int, nb: int, s_steps: int,
    vk: int, vn: int, in_itemsize: int = 4, w_itemsize: int = 4,
    out_itemsize: int = 4, residual_bytes: int = 0,
) -> pl.CostEstimate:
    """Kernel-side cost of the row-tap stack impl (stack *build* excluded —
    that extra pass is modeled in `core.accel_model.conv_layer_traffic`).

    Every sparse step changes the (plane, cin-tile) block index, so the
    input block (bh, bw, vk) is DMA'd on every one of the NB*S steps per
    row-block.
    """
    hb = hop // bh
    return pl.CostEstimate(
        flops=2 * n * hop * w_out * nb * s_steps * vk * vn,
        bytes_accessed=(
            n * hb * nb * s_steps * bh * bw * vk * in_itemsize
            + nb * s_steps * vk * vn * w_itemsize
            + n * hop * w_out * nb * vn * out_itemsize
            + residual_bytes
        ),
        transcendentals=0,
    )


# Below this output height the per-strip halo fetch floor (min(S, cb)
# re-fetches of a window that is mostly the whole padded input) stops
# amortizing; the halo kernel switches to the resident whole-input layout.
RESIDENT_MAX_H = 4


def use_resident_halo(h_out: int, groups: int) -> bool:
    """True when the halo impl runs the tiny-feature-map resident layout:
    the (padded) output height fits one VMEM-resident block of *all* cin
    tiles, fetched once per (image, row-block) — grid reordered row-block
    outermost so every strip and sparse step revisits it DMA-free.
    Grouped convs keep the per-group streaming layout (a resident block
    would fetch other groups' channels)."""
    return h_out < RESIDENT_MAX_H and groups == 1


def halo_block_rows(kh: int, stride: int, bh: int, dilation: int = 1) -> int:
    """Phase rows in one halo block: the ``bh`` output rows plus the taps'
    reach, ``((kh-1)*dilation) // stride`` rows, in every phase plane."""
    return bh + ((kh - 1) * dilation) // stride


def halo_kernel_cost(
    *, n: int, hop: int, w_out: int, kh: int, stride: int, bwp: int, bh: int,
    nb: int, s_steps: int, cb: int, vk: int, vn: int, dilation: int = 1,
    resident: bool = False, in_itemsize: int = 4, w_itemsize: int = 4,
    out_itemsize: int = 4, residual_bytes: int = 0,
) -> pl.CostEstimate:
    """Kernel-side cost of the halo impl (``bwp`` is the phase-plane width
    `halo_layout_dims` gives).

    The halo block offset depends only on (row-block, cin tile): with the
    stored tiles cin-major per strip, consecutive taps of one cin tile
    revisit the same block (no DMA), so each of the min(S, cb) distinct cin
    tiles is fetched once per (strip, row-block) — a halo block of
    ``stride**2`` phase planes x `halo_block_rows` rows instead of S
    fetches of bh rows.  ``cb`` is the cin tiles *reachable from one
    strip* — Cin/vk for an ungrouped conv, Cin/(groups*vk) for a grouped
    one (a strip only ever touches its own group's channels, the per-group
    fetch accounting).

    ``resident`` is the tiny-feature-map layout (`use_resident_halo`): one
    block holding *all* ``cb`` cin tiles, offset independent of both strip
    and sparse step, with the row-block grid axis outermost — fetched once
    per (image, row-block), no per-strip re-fetch at all.
    """
    hb = hop // bh
    block = stride * stride * halo_block_rows(kh, stride, bh, dilation) * bwp
    if resident:
        input_bytes = n * hb * block * cb * vk * in_itemsize
    else:
        input_bytes = n * hb * nb * min(s_steps, cb) * block * vk \
            * in_itemsize
    return pl.CostEstimate(
        flops=2 * n * hop * w_out * nb * s_steps * vk * vn,
        bytes_accessed=(
            input_bytes
            + nb * s_steps * vk * vn * w_itemsize
            + n * hop * w_out * nb * vn * out_itemsize
            + residual_bytes
        ),
        transcendentals=0,
    )


def dw_halo_kernel_cost(
    *, n: int, hop: int, w_out: int, kh: int, stride: int, bwp: int, bh: int,
    nb: int, s_steps: int, vc: int, dilation: int = 1, in_itemsize: int = 4,
    w_itemsize: int = 4, out_itemsize: int = 4, residual_bytes: int = 0,
) -> pl.CostEstimate:
    """Kernel-side cost of the depthwise halo impl.

    The halo block offset depends only on (row-block, channel tile) — not
    the tap at all — so every sparse step of strip j revisits the same
    block: exactly ONE halo fetch per (strip, row-block), whatever the tap
    order.  MACs are elementwise (VPU), one per (pixel, channel, stored
    tap).
    """
    hb = hop // bh
    block = stride * stride * halo_block_rows(kh, stride, bh, dilation) * bwp
    return pl.CostEstimate(
        flops=2 * n * hop * w_out * nb * s_steps * vc,
        bytes_accessed=(
            n * hb * nb * block * vc * in_itemsize
            + nb * s_steps * vc * w_itemsize
            + n * hop * w_out * nb * vc * out_itemsize
            + residual_bytes
        ),
        transcendentals=0,
    )


def dw_stack_kernel_cost(
    *, n: int, hop: int, w_out: int, bw: int, bh: int, nb: int, s_steps: int,
    vc: int, in_itemsize: int = 4, w_itemsize: int = 4, out_itemsize: int = 4,
    residual_bytes: int = 0,
) -> pl.CostEstimate:
    """Kernel-side cost of the depthwise row-tap-stack impl: every sparse
    step changes the (plane, channel-tile) block index, so the (bh, bw, vc)
    input block is DMA'd on every one of the S steps per row-block."""
    hb = hop // bh
    return pl.CostEstimate(
        flops=2 * n * hop * w_out * nb * s_steps * vc,
        bytes_accessed=(
            n * hb * nb * s_steps * bh * bw * vc * in_itemsize
            + nb * s_steps * vc * w_itemsize
            + n * hop * w_out * nb * vc * out_itemsize
            + residual_bytes
        ),
        transcendentals=0,
    )


# --------------------------------------------------------------------------
# BlockSpec index maps (named factories — shared with `repro.analysis`)
# --------------------------------------------------------------------------
#
# Every index map below is closed arithmetic (+ - * // %) over the grid
# indices and the prefetched idx table, with a uniform (g0, g1, g2, idx)
# signature in *grid order*.  Naming them (instead of inlining lambdas in
# the pallas_call specs) lets the static analyzer evaluate the exact same
# functions abstractly — over `analysis.intervals.Interval` grid axes for
# the in-bounds proof, and over concrete numpy index arrays for the
# DMA-byte derivation — so the kernels and their verifier can never use
# different offset arithmetic.
#
# Grid orders: streaming conv kernels (j, m, s) = (cout strip,
# image*row-block, sparse step); the resident halo kernel (m, j, s) with
# the row-block outermost; vsmm (j, mi, s).


def halo_in_index_map(hb: int, bh: int, cbg: int, spg: int):
    """Streaming halo input (element offsets, `pl.Element`): one image, one
    cin tile, every phase, one overlapping halo row window, full width.
    The offset is tap-independent, so consecutive sparse steps on one cin
    tile revisit the block without a new DMA; a grouped strip adds its
    group's base cin tile."""
    def index_map(j, m, s, idx):
        return (
            m // hb,                             # image
            (j // spg) * cbg + idx[j, s] % cbg,  # cin tile (+ group base)
            0,                                   # every phase
            (m % hb) * bh,                       # halo window start row
            0,
            0,
        )
    return index_map


def resident_in_index_map(hb: int, bh: int):
    """Resident (tiny-feature-map) halo input: one block holding ALL cin
    tiles, offset a function of the row-block only — with the
    (image, row-block) grid axis outermost the block is DMA'd exactly once
    per (image, row-block)."""
    def index_map(m, j, s, idx):
        return (m // hb, 0, 0, (m % hb) * bh, 0, 0)
    return index_map


def dw_halo_in_index_map(hb: int, bh: int):
    """Depthwise halo input: strip j IS the channel tile; the offset is
    tap-independent, so the halo is fetched once per (strip, row-block)."""
    def index_map(j, m, s, idx):
        return (m // hb, j, 0, (m % hb) * bh, 0, 0)
    return index_map


def stack_in_index_map(hb: int, cbg: int, spg: int, kw: int, stride: int,
                       dilation: int):
    """Row-tap stack input (block indices): the plane id is the generalized
    tap select ``ky*stride + (kx*dilation) % stride`` decoded from the
    stored tile id, plus the strip's group-based cin tile."""
    def index_map(j, m, s, idx):
        t = idx[j, s]
        return (
            m // hb,                                            # image
            (t // cbg // kw) * stride
            + (((t // cbg) % kw) * dilation) % stride,          # (ky, phase)
            m % hb,                                             # row block
            0,
            (j // spg) * cbg + t % cbg,                         # cin tile
        )
    return index_map


def dw_stack_in_index_map(hb: int, kw: int, stride: int, dilation: int):
    """Depthwise row-tap stack input: idx[j, s] is the bare tap id and the
    strip is the channel tile."""
    def index_map(j, m, s, idx):
        t = idx[j, s]
        return (
            m // hb,
            (t // kw) * stride + ((t % kw) * dilation) % stride,  # (ky, ph)
            m % hb,
            0,
            j,
        )
    return index_map


def conv_weight_index_map(resident: bool = False):
    """The s-th stored weight tile of strip j (both conv grid orders)."""
    if resident:
        def index_map(m, j, s, idx):
            return (j, s, 0, 0)
    else:
        def index_map(j, m, s, idx):
            return (j, s, 0, 0)
    return index_map


def conv_out_index_map(hb: int, resident: bool = False):
    """Output/residual row-block tile of (strip j, image*row-block m)."""
    if resident:
        def index_map(m, j, s, idx):
            return (m // hb, m % hb, 0, j)
    else:
        def index_map(j, m, s, idx):
            return (m // hb, m % hb, 0, j)
    return index_map


def conv_bias_index_map(resident: bool = False):
    """Strip j's (1, 1, vn) bias tile (excluded from the byte contract: one
    tile per strip, noise next to the input/weight/output terms)."""
    if resident:
        def index_map(m, j, s, idx):
            return (j, 0, 0)
    else:
        def index_map(j, m, s, idx):
            return (j, 0, 0)
    return index_map


def halo_layout_dims(h: int, w: int, *, kh: int, kw: int, stride: int,
                     dilation: int, h_out: int) -> tuple[int, int]:
    """(rows, bW) of one phase plane of `build_halo_input`'s buffer for the
    given geometry — the single source the builder, the cost model, and
    the analyzer's bounds proof all share.  A tap reaches
    ``((k-1)*dilation) // stride`` phase rows/columns past the output
    extent.  bW needs no rounding: the halo block spans the whole width,
    and a block dim equal to the array's is always legal on the TPU."""
    wo, _, _ = same_pads(w, kw, stride, dilation)
    rows = h_out + ((kh - 1) * dilation) // stride
    bw = wo + ((kw - 1) * dilation) // stride
    return rows, bw


def stack_layout_dims(h: int, w: int, *, kh: int, kw: int, stride: int,
                      dilation: int, h_out: int, sublane: int = 8
                      ) -> tuple[int, int]:
    """(planes, bW) of `build_row_tap_stack`'s materialized buffer."""
    wo, _, _ = same_pads(w, kw, stride, dilation)
    bw = -(-(wo + ((kw - 1) * dilation) // stride) // sublane) * sublane
    return kh * stride, bw


# --------------------------------------------------------------------------
# Input layouts
# --------------------------------------------------------------------------

def build_halo_input(
    x: jax.Array,
    *,
    kh: int = 3,
    kw: int = 3,
    stride: int = 1,
    dilation: int = 1,
    vk: int,
    h_out: int | None = None,
) -> jax.Array:
    """NHWC -> (N, CB, stride**2, rows, bW, vk) SAME-padded, phase-split
    direct input for the halo kernel: phase ``py*stride + px`` holds padded
    pixels ``(stride*r + py, stride*q + px)``.  One pad + transpose (XLA
    fuses them into the only HBM copy of the layout); (rows, bW) per phase
    plane come from `halo_layout_dims`, so every halo block and in-kernel
    tap window stays in bounds.

    ``h_out`` lets the caller round Hout up to a row-block multiple (the
    extra rows read zero padding).
    """
    n, h, w, c = x.shape
    assert c % vk == 0, (c, vk)
    ho, pt, _ = same_pads(h, kh, stride, dilation)
    _, pl_, _ = same_pads(w, kw, stride, dilation)
    ho = h_out or ho
    rows, bw = halo_layout_dims(h, w, kh=kh, kw=kw, stride=stride,
                                dilation=dilation, h_out=ho)
    s = stride
    xp = jnp.pad(
        x,
        ((0, 0), (pt, max(s * rows - h - pt, 0)),
         (pl_, max(s * bw - w - pl_, 0)), (0, 0)),
    )[:, :s * rows, :s * bw]
    xp = xp.reshape(n, rows, s, bw, s, c // vk, vk)
    return xp.transpose(0, 5, 2, 4, 1, 3, 6).reshape(
        n, c // vk, s * s, rows, bw, vk)


def build_row_tap_stack(
    x: jax.Array,
    *,
    kh: int = 3,
    kw: int = 3,
    stride: int = 1,
    dilation: int = 1,
    h_out: int | None = None,
    sublane: int = 8,
) -> jax.Array:
    """NHWC -> (N, kh*stride, Hout, bW, C) row-tap/phase stack (SAME padding).

    The stack-impl (oracle) layout: kh*stride output-sized planes
    materialized in HBM; tap row ky reads padded rows ky*dilation + stride*i
    (dilation spaces the taps, the plane count stays kh*stride).  ``h_out``
    lets the caller round Hout up to a row-block multiple (the extra rows
    read zero padding).  bW = Wout + ((kw-1)*dilation)//stride rounded up to
    ``sublane`` so the kernel's kx slice stays in-bounds and
    sublane-aligned.
    """
    n, h, w, c = x.shape
    ho, pt, _ = same_pads(h, kh, stride, dilation)
    _, pl_, _ = same_pads(w, kw, stride, dilation)
    ho = h_out or ho
    _, bw = stack_layout_dims(h, w, kh=kh, kw=kw, stride=stride,
                              dilation=dilation, h_out=ho, sublane=sublane)
    # padded-row index ceiling (effective kernel extent)
    rows_needed = stride * (ho - 1) + (kh - 1) * dilation + 1
    cols_needed = stride * bw  # every phase plane must reach bw columns
    xp = jnp.pad(
        x,
        (
            (0, 0),
            (pt, max(rows_needed - h - pt, 0)),
            (pl_, max(cols_needed - w - pl_, 0)),
            (0, 0),
        ),
    )
    planes = [
        xp[:, ky * dilation : ky * dilation + stride * (ho - 1) + 1 : stride,
           phase :: stride][:, :, :bw]
        for ky in range(kh)
        for phase in range(stride)
    ]
    return jnp.stack(planes, axis=1)


# --------------------------------------------------------------------------
# Halo kernel (default): direct input, tap resolved in-kernel
# --------------------------------------------------------------------------

def _tap_window(x_ref, ct, ky, kx: int, *, stride: int, dilation: int,
                bh: int, w_out: int) -> jax.Array:
    """The (bh, w_out, C) input window of tap (ky, kx) from a phase-split
    halo block: phase ((ky*d) % stride, (kx*d) % stride), rows from
    (ky*d) // stride, columns from (kx*d) // stride.  ``ky`` is dynamic (a
    leading-axis offset); ``kx`` is static, so the column offset on the
    sublane axis is a constant — the TPU compiler takes dynamic sublane
    offsets for 32-bit data only.  int8 windows are widened (`_widen`)."""
    py, r0 = (ky * dilation) % stride, (ky * dilation) // stride
    px, c0 = (kx * dilation) % stride, (kx * dilation) // stride
    return _widen(x_ref[0, ct, py * stride + px, pl.ds(r0, bh),
                        pl.ds(c0, w_out), :])


def _halo_kernel(idx_ref, xh_ref, w_ref, *refs, cb: int, kw: int, stride: int,
                 dilation: int, bh: int, w_out: int, resident: bool,
                 fuse_relu: bool, has_scale: bool, has_bias: bool,
                 has_residual: bool, skip_zero_inputs: bool):
    scale_ref, bias_ref, res_ref, o_ref, acc_ref = _unpack_refs(
        refs, has_scale=has_scale, has_bias=has_bias,
        has_residual=has_residual)
    # streaming grid (j, m, s); resident grid (m, j, s), row-block outermost
    j = pl.program_id(1 if resident else 0)
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # decode the K-tile id t = (ky*kw + kx) * cb + cin_tile (cb = cin tiles
    # reachable from this strip — per group for a grouped conv).  Streaming:
    # the index_map already selected the cin tile.  Resident: the block
    # holds every cin tile and it is a dynamic leading-axis index here.
    t = idx_ref[j, s]
    tap = t // cb
    ky = tap // kw
    ct = t % cb if resident else 0
    for kx in range(kw):
        @pl.when(tap % kw == kx)
        def _tap(kx=kx):
            xt = _tap_window(xh_ref, ct, ky, kx, stride=stride,
                             dilation=dilation, bh=bh, w_out=w_out)
            xs2 = xt.reshape(bh * w_out, xt.shape[-1])

            def _mac():
                acc_ref[...] += _mac_dot(xs2, w_ref[0, 0])

            if skip_zero_inputs:
                # paper's input zero-vector skip (post-ReLU activations)
                pl.when(_nonzero(xs2))(_mac)
            else:
                _mac()

    @pl.when(s == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = _epilogue(
            acc_ref[...].reshape(o_ref.shape), scale_ref, bias_ref, res_ref,
            fuse_relu=fuse_relu).astype(o_ref.dtype)


def _element_block(shape: tuple[int, ...], index_map) -> pl.BlockSpec:
    """A BlockSpec whose index_map yields element offsets on every axis
    (`pl.Element`), for the overlapping halo row windows."""
    return pl.BlockSpec(tuple(pl.Element(d) for d in shape), index_map)


@functools.partial(
    jax.jit,
    static_argnames=(
        "kh", "kw", "stride", "groups", "dilation", "w_out", "bh",
        "skip_zero_inputs", "fuse_relu", "interpret", "out_dtype",
    ),
)
def vsconv_halo_pallas(
    xh: jax.Array,
    vs: VectorSparse,
    *,
    w_out: int,
    kh: int = 3,
    kw: int = 3,
    stride: int = 1,
    groups: int = 1,
    dilation: int = 1,
    bias: jax.Array | None = None,
    residual: jax.Array | None = None,
    scale: jax.Array | None = None,
    bh: int = 8,
    skip_zero_inputs: bool = True,
    fuse_relu: bool = False,
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    """Direct input xh (N, CB, stride**2, rows, bW, vk) * sparse
    (kh*kw*CB*vk/groups, Cout) -> (N, Hout, w_out, Cout), Hout = rows -
    ((kh-1)*dilation) // stride.

    INT8: int8 ``xh`` + int8 ``vs.vals`` + ``scale`` (Cout,) — the combined
    per-cout dequant scale, applied at flush before the bias; each step's
    MAC is exact (`_mac_dot`) and the output defaults to f32.

    ``xh`` is `build_halo_input`'s SAME-padded, phase-split raw input; Hout
    must be a multiple of ``bh`` (the `ops.vsconv` wrapper pads).  Each grid
    step sees an overlapping `halo_block_rows`-row halo block of every
    phase (`pl.Element` element offsets) and slices its tap out in-kernel,
    so no tap-shifted copy of the input ever exists in HBM.  ``groups``
    shards the cin-tile axis: output strip j belongs to group j // (NB/groups)
    and its stored K-tile ids index that group's CB/groups cin tiles only
    (the index_map adds the group's base tile).  ``bias`` (Cout,),
    ``residual`` (N, Hout, w_out, Cout) and ``fuse_relu`` run the epilogue
    at flush time, identically to the stack kernel.
    """
    n, cb, phases, rows, bwp, vk = xh.shape
    assert phases == stride * stride, (xh.shape, stride)
    h = rows - ((kh - 1) * dilation) // stride
    nb, s_steps, vk_w, vn = vs.vals.shape
    assert cb % groups == 0 and nb % groups == 0, (cb, nb, groups)
    cbg = cb // groups   # cin tiles reachable from one strip
    spg = nb // groups   # output strips per group
    assert vk_w == vk and vs.shape[0] == kh * kw * cbg * vk, (
        vs.shape, xh.shape, kh, kw, groups)
    assert h % bh == 0, (h, bh)
    hb = h // bh
    hh = halo_block_rows(kh, stride, bh, dilation)
    out_dtype = out_dtype or (jnp.float32 if xh.dtype == jnp.int8
                              else xh.dtype)
    has_residual = residual is not None
    resident = use_resident_halo(h, groups)

    if resident:
        # tiny-feature-map layout: ONE block of all cb cin tiles, offsets a
        # function of the row-block only — with the (image, row-block) axis
        # outermost every strip and sparse step revisits it, so the input
        # is DMA'd exactly once per (image, row-block)
        x_spec = _element_block((1, cb, phases, hh, bwp, vk),
                                resident_in_index_map(hb, bh))
        grid = (n * hb, nb, s_steps)
    else:
        # one image, one cin tile, every phase, one overlapping halo row
        # window, full width: row-blocks overlap by hh - bh rows, and the
        # offsets are tap-independent so consecutive sparse steps on one
        # cin tile revisit the block without a new DMA (cin-major tile
        # order makes that the common case).  A grouped strip's tile id is
        # relative to its group, so the group's base tile is added here.
        x_spec = _element_block((1, 1, phases, hh, bwp, vk),
                                halo_in_index_map(hb, bh, cbg, spg))
        grid = (nb, n * hb, s_steps)
    out_map = conv_out_index_map(hb, resident=resident)
    in_specs = [x_spec,
                pl.BlockSpec((1, 1, vk, vn),
                             conv_weight_index_map(resident=resident))]
    args = [vs.idx, xh, vs.vals]
    epilogue_operands(in_specs, args, nb=nb, vn=vn,
                      bias_map=conv_bias_index_map(resident=resident),
                      scale=scale, bias=bias)
    if has_residual:
        assert residual.shape == (n, h, w_out, nb * vn), (
            residual.shape, (n, h, w_out, nb * vn))
        in_specs.append(pl.BlockSpec((1, bh, w_out, vn), out_map))
        args.append(residual)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bh, w_out, vn), out_map),
        scratch_shapes=[pltpu.VMEM((bh * w_out, vn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(
            _halo_kernel, cb=cbg, kw=kw, stride=stride,
            dilation=dilation, bh=bh, w_out=w_out, resident=resident,
            fuse_relu=fuse_relu, has_scale=scale is not None,
            has_bias=bias is not None, has_residual=has_residual,
            skip_zero_inputs=skip_zero_inputs,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, h, w_out, nb * vn), out_dtype),
        interpret=interpret,
        cost_estimate=halo_kernel_cost(
            n=n, hop=h, w_out=w_out, kh=kh, stride=stride, bwp=bwp, bh=bh,
            nb=nb, s_steps=s_steps, cb=cbg, vk=vk, vn=vn, dilation=dilation,
            resident=resident,
            in_itemsize=xh.dtype.itemsize,
            w_itemsize=vs.vals.dtype.itemsize,
            out_itemsize=jnp.dtype(out_dtype).itemsize,
            residual_bytes=(residual.size * residual.dtype.itemsize
                            if has_residual else 0),
        ),
    )(*args)


# --------------------------------------------------------------------------
# Row-tap stack kernel (oracle + fallback)
# --------------------------------------------------------------------------

def _kernel(idx_ref, xt_ref, w_ref, *refs, cb: int, kw: int, stride: int,
            dilation: int, w_out: int, fuse_relu: bool, has_scale: bool,
            has_bias: bool, has_residual: bool, skip_zero_inputs: bool):
    scale_ref, bias_ref, res_ref, o_ref, acc_ref = _unpack_refs(
        refs, has_scale=has_scale, has_bias=has_bias,
        has_residual=has_residual)
    j = pl.program_id(0)
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # decode the K-tile id: t = (ky*kw + kx) * cb + cin_tile (cb per group
    # for a grouped conv).  ky and the width phase ((kx*dilation) % stride)
    # are already resolved by the index_map; only the in-plane column
    # offset (kx*dilation) // stride remains.
    t = idx_ref[j, s]
    kx = (t // cb) % kw

    xt = xt_ref[0, 0]  # (bh, bW, vk) — plane and cin-tile selected by index_map
    xs = jax.lax.dynamic_slice_in_dim(
        xt, (kx * dilation) // stride, w_out, axis=1)
    xs2 = xs.reshape(-1, xs.shape[-1])  # (bh*w_out, vk)

    def _mac():
        acc_ref[...] += _mac_dot(xs2, w_ref[0, 0])

    if skip_zero_inputs:
        # paper's input zero-vector skip (post-ReLU activations)
        pl.when(_nonzero(xs2))(_mac)
    else:
        _mac()

    @pl.when(s == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = _epilogue(
            acc_ref[...].reshape(o_ref.shape), scale_ref, bias_ref, res_ref,
            fuse_relu=fuse_relu).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "kh", "kw", "stride", "groups", "dilation", "w_out", "bh",
        "skip_zero_inputs", "fuse_relu", "interpret", "out_dtype",
    ),
)
def vsconv_pallas(
    xt: jax.Array,
    vs: VectorSparse,
    *,
    w_out: int,
    kh: int = 3,
    kw: int = 3,
    stride: int = 1,
    groups: int = 1,
    dilation: int = 1,
    bias: jax.Array | None = None,
    residual: jax.Array | None = None,
    scale: jax.Array | None = None,
    bh: int = 8,
    skip_zero_inputs: bool = True,
    fuse_relu: bool = False,
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    """Row-tap stack xt (N, kh*stride, H, bW, C) * sparse (kh*kw*C/groups,
    Cout) -> (N, H, w_out, Cout).

    The materialized-stack impl, kept as the oracle/fallback for
    `vsconv_halo_pallas`.  H (the stack's output-row count) must be a
    multiple of ``bh``; the `ops.vsconv` wrapper pads.  ``groups`` shards
    the cin-tile axis per group exactly as in the halo kernel; ``dilation``
    spaces the taps (the stack planes are built dilated, so only the
    in-plane column offset changes here).  ``bias`` (Cout,), ``residual``
    (N, H, w_out, Cout) — the ResNet shortcut, added before the ReLU — and
    ``fuse_relu`` run the epilogue inside the kernel at flush time.
    """
    n, planes, h, bw, c = xt.shape
    assert planes == kh * stride, (planes, kh, stride)
    nb, s_steps, vk, vn = vs.vals.shape
    assert c % vk == 0 and (c // vk) % groups == 0 and nb % groups == 0, (
        c, vk, nb, groups)
    cbg = (c // vk) // groups  # cin-tiles per tap reachable from one strip
    spg = nb // groups         # output strips per group
    assert vs.shape[0] == kh * kw * cbg * vk, (vs.shape, c, vk, groups)
    assert h % bh == 0, (h, bh)
    hb = h // bh
    out_dtype = out_dtype or (jnp.float32 if xt.dtype == jnp.int8
                              else xt.dtype)
    has_scale = scale is not None
    has_bias = bias is not None
    has_residual = residual is not None

    in_specs = [
        # block: one image, one (ky, phase) plane, one row block, full width,
        # one cin tile — the plane id is the generalized tap select:
        #   plane = ky*stride + (kx*dilation) % stride,  tap = idx[j,s] // cbg
        # and a grouped strip's cin tile gets its group's base added.
        pl.BlockSpec(
            (1, 1, bh, bw, vk),
            stack_in_index_map(hb, cbg, spg, kw, stride, dilation),
        ),
        pl.BlockSpec((1, 1, vk, vn), conv_weight_index_map()),
    ]
    args = [vs.idx, xt, vs.vals]
    epilogue_operands(in_specs, args, nb=nb, vn=vn,
                      bias_map=conv_bias_index_map(), scale=scale, bias=bias)
    if has_residual:
        assert residual.shape == (n, h, w_out, nb * vn), (
            residual.shape, (n, h, w_out, nb * vn))
        in_specs.append(pl.BlockSpec((1, bh, w_out, vn), conv_out_index_map(hb)))
        args.append(residual)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb, n * hb, s_steps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bh, w_out, vn), conv_out_index_map(hb)),
        scratch_shapes=[pltpu.VMEM((bh * w_out, vn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(
            _kernel, cb=cbg, kw=kw, stride=stride, dilation=dilation,
            w_out=w_out,
            fuse_relu=fuse_relu, has_scale=has_scale, has_bias=has_bias,
            has_residual=has_residual,
            skip_zero_inputs=skip_zero_inputs,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, h, w_out, nb * vn), out_dtype),
        interpret=interpret,
        cost_estimate=stack_kernel_cost(
            n=n, hop=h, w_out=w_out, bw=bw, bh=bh, nb=nb, s_steps=s_steps,
            vk=vk, vn=vn, in_itemsize=xt.dtype.itemsize,
            w_itemsize=vs.vals.dtype.itemsize,
            out_itemsize=jnp.dtype(out_dtype).itemsize,
            residual_bytes=(residual.size * residual.dtype.itemsize
                            if has_residual else 0),
        ),
    )(*args)


# --------------------------------------------------------------------------
# Depthwise kernels (groups == Cin): per-channel tap vectors, VPU MACs
# --------------------------------------------------------------------------
#
# A depthwise conv (multiplier 1) has one kh x kw filter per channel — a
# full-cin K-tile would waste vk-1 lanes of every MXU issue.  Instead the
# weight is the (kh*kw, C) tap matrix encoded with vk == 1, vn == vc: output
# strips are vc-channel tiles and each stored vector is one tap's weights
# across the tile (idx[j, s] = the tap id).  The MAC is elementwise over the
# channel lane axis (VPU, not MXU); pruned (tap, channel-tile) vectors are
# structurally absent and an all-zero shifted input block is skipped with
# @pl.when — the same two-sided skip as the full kernels.


def _dw_halo_kernel(idx_ref, xh_ref, w_ref, *refs, kw: int, stride: int,
                    dilation: int, bh: int, w_out: int, fuse_relu: bool,
                    has_scale: bool, has_bias: bool, has_residual: bool,
                    skip_zero_inputs: bool):
    scale_ref, bias_ref, res_ref, o_ref, acc_ref = _unpack_refs(
        refs, has_scale=has_scale, has_bias=has_bias,
        has_residual=has_residual)
    j = pl.program_id(0)
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # idx[j, s] IS the tap id — no cin tile to decode: the input block
    # depends only on (row-block, channel tile), so every sparse step
    # revisits it and the halo is fetched exactly once per (strip, block).
    t = idx_ref[j, s]
    ky = t // kw
    for kx in range(kw):
        @pl.when(t % kw == kx)
        def _tap(kx=kx):
            xt = _tap_window(xh_ref, 0, ky, kx, stride=stride,
                             dilation=dilation, bh=bh, w_out=w_out)

            def _mac():
                # elementwise per-channel MAC: one tap vector scales its
                # channels (f32-exact for int8 values too — every
                # |v| <= 127 product is exactly representable)
                acc_ref[...] += xt.astype(jnp.float32) * w_ref[0, 0].astype(
                    jnp.float32)

            if skip_zero_inputs:
                pl.when(_nonzero(xt))(_mac)
            else:
                _mac()

    @pl.when(s == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = _epilogue(
            acc_ref[...].reshape(o_ref.shape), scale_ref, bias_ref, res_ref,
            fuse_relu=fuse_relu).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "kh", "kw", "stride", "dilation", "w_out", "bh", "skip_zero_inputs",
        "fuse_relu", "interpret", "out_dtype",
    ),
)
def vsconv_dw_halo_pallas(
    xh: jax.Array,
    vs: VectorSparse,
    *,
    w_out: int,
    kh: int = 3,
    kw: int = 3,
    stride: int = 1,
    dilation: int = 1,
    bias: jax.Array | None = None,
    residual: jax.Array | None = None,
    scale: jax.Array | None = None,
    bh: int = 8,
    skip_zero_inputs: bool = True,
    fuse_relu: bool = False,
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    """Depthwise halo kernel: direct input xh (N, CB, stride**2, rows, bW,
    vc) * tap matrix (kh*kw, C) encoded vk=1/vn=vc -> (N, Hout, w_out, C).

    ``xh`` is `build_halo_input(x, vk=vc)`; the channel-tile axis CB = C/vc
    is the strip axis.  The halo block offset is tap-independent AND
    cin-tile-trivial (strip == channel tile), so the input is DMA'd once
    per (strip, row-block) regardless of tap order — the depthwise case is
    where the halo layout's fetch-once story is exact, not amortized.
    """
    n, cb, phases, rows, bwp, vc = xh.shape
    assert phases == stride * stride, (xh.shape, stride)
    h = rows - ((kh - 1) * dilation) // stride
    nb, s_steps, vk_w, vn = vs.vals.shape
    assert vk_w == 1 and vn == vc and nb == cb, (vs.vals.shape, xh.shape)
    assert vs.shape == (kh * kw, cb * vc), (vs.shape, kh, kw, cb, vc)
    assert h % bh == 0, (h, bh)
    hb = h // bh
    hh = halo_block_rows(kh, stride, bh, dilation)
    out_dtype = out_dtype or (jnp.float32 if xh.dtype == jnp.int8
                              else xh.dtype)
    has_scale = scale is not None
    has_bias = bias is not None
    has_residual = residual is not None

    in_specs = [
        _element_block((1, 1, phases, hh, bwp, vc),
                       dw_halo_in_index_map(hb, bh)),
        pl.BlockSpec((1, 1, 1, vc), conv_weight_index_map()),
    ]
    args = [vs.idx, xh, vs.vals]
    epilogue_operands(in_specs, args, nb=nb, vn=vc,
                      bias_map=conv_bias_index_map(), scale=scale, bias=bias)
    if has_residual:
        assert residual.shape == (n, h, w_out, nb * vc), (
            residual.shape, (n, h, w_out, nb * vc))
        in_specs.append(pl.BlockSpec((1, bh, w_out, vc), conv_out_index_map(hb)))
        args.append(residual)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb, n * hb, s_steps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bh, w_out, vc), conv_out_index_map(hb)),
        scratch_shapes=[pltpu.VMEM((bh, w_out, vc), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(
            _dw_halo_kernel, kw=kw, stride=stride, dilation=dilation, bh=bh,
            w_out=w_out, fuse_relu=fuse_relu, has_scale=has_scale,
            has_bias=has_bias, has_residual=has_residual,
            skip_zero_inputs=skip_zero_inputs,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, h, w_out, nb * vc), out_dtype),
        interpret=interpret,
        cost_estimate=dw_halo_kernel_cost(
            n=n, hop=h, w_out=w_out, kh=kh, stride=stride, bwp=bwp, bh=bh,
            nb=nb, s_steps=s_steps, vc=vc, dilation=dilation,
            in_itemsize=xh.dtype.itemsize,
            w_itemsize=vs.vals.dtype.itemsize,
            out_itemsize=jnp.dtype(out_dtype).itemsize,
            residual_bytes=(residual.size * residual.dtype.itemsize
                            if has_residual else 0),
        ),
    )(*args)


def _dw_stack_kernel(idx_ref, xt_ref, w_ref, *refs, kw: int, stride: int,
                     dilation: int, w_out: int, fuse_relu: bool,
                     has_scale: bool, has_bias: bool, has_residual: bool,
                     skip_zero_inputs: bool):
    scale_ref, bias_ref, res_ref, o_ref, acc_ref = _unpack_refs(
        refs, has_scale=has_scale, has_bias=has_bias,
        has_residual=has_residual)
    j = pl.program_id(0)
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # idx[j, s] is the tap id; (ky, phase) resolved by the index_map, only
    # the in-plane column offset remains.
    t = idx_ref[j, s]
    kx = t % kw
    xt = xt_ref[0, 0]  # (bh, bW, vc)
    xs = jax.lax.dynamic_slice_in_dim(
        xt, (kx * dilation) // stride, w_out, axis=1)
    xs2 = xs.reshape(-1, xs.shape[-1])

    def _mac():
        acc_ref[...] += xs2.astype(jnp.float32) * w_ref[0, 0, 0].astype(
            jnp.float32)

    if skip_zero_inputs:
        pl.when(_nonzero(xs2))(_mac)
    else:
        _mac()

    @pl.when(s == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = _epilogue(
            acc_ref[...].reshape(o_ref.shape), scale_ref, bias_ref, res_ref,
            fuse_relu=fuse_relu).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "kh", "kw", "stride", "dilation", "w_out", "bh", "skip_zero_inputs",
        "fuse_relu", "interpret", "out_dtype",
    ),
)
def vsconv_dw_stack_pallas(
    xt: jax.Array,
    vs: VectorSparse,
    *,
    w_out: int,
    kh: int = 3,
    kw: int = 3,
    stride: int = 1,
    dilation: int = 1,
    bias: jax.Array | None = None,
    residual: jax.Array | None = None,
    scale: jax.Array | None = None,
    bh: int = 8,
    skip_zero_inputs: bool = True,
    fuse_relu: bool = False,
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    """Depthwise row-tap-stack kernel: xt (N, kh*stride, H, bW, C) * tap
    matrix (kh*kw, C) encoded vk=1/vn=vc -> (N, H, w_out, C).

    The bandwidth-dumb oracle for `vsconv_dw_halo_pallas`: each sparse step
    selects a fresh (plane, channel-tile) block, so the input is re-DMA'd
    every step — S fetches where the halo layout needs one.
    """
    n, planes, h, bw, c = xt.shape
    assert planes == kh * stride, (planes, kh, stride)
    nb, s_steps, vk_w, vc = vs.vals.shape
    assert vk_w == 1 and c == nb * vc, (vs.vals.shape, c)
    assert vs.shape == (kh * kw, c), (vs.shape, kh, kw, c)
    assert h % bh == 0, (h, bh)
    hb = h // bh
    out_dtype = out_dtype or (jnp.float32 if xt.dtype == jnp.int8
                              else xt.dtype)
    has_scale = scale is not None
    has_bias = bias is not None
    has_residual = residual is not None

    in_specs = [
        pl.BlockSpec(
            (1, 1, bh, bw, vc),
            dw_stack_in_index_map(hb, kw, stride, dilation),
        ),
        pl.BlockSpec((1, 1, 1, vc), conv_weight_index_map()),
    ]
    args = [vs.idx, xt, vs.vals]
    epilogue_operands(in_specs, args, nb=nb, vn=vc,
                      bias_map=conv_bias_index_map(), scale=scale, bias=bias)
    if has_residual:
        assert residual.shape == (n, h, w_out, c), (
            residual.shape, (n, h, w_out, c))
        in_specs.append(pl.BlockSpec((1, bh, w_out, vc), conv_out_index_map(hb)))
        args.append(residual)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb, n * hb, s_steps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bh, w_out, vc), conv_out_index_map(hb)),
        scratch_shapes=[pltpu.VMEM((bh * w_out, vc), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(
            _dw_stack_kernel, kw=kw, stride=stride, dilation=dilation,
            w_out=w_out, fuse_relu=fuse_relu, has_scale=has_scale,
            has_bias=has_bias, has_residual=has_residual,
            skip_zero_inputs=skip_zero_inputs,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, h, w_out, c), out_dtype),
        interpret=interpret,
        cost_estimate=dw_stack_kernel_cost(
            n=n, hop=h, w_out=w_out, bw=bw, bh=bh, nb=nb, s_steps=s_steps,
            vc=vc, in_itemsize=xt.dtype.itemsize,
            w_itemsize=vs.vals.dtype.itemsize,
            out_itemsize=jnp.dtype(out_dtype).itemsize,
            residual_bytes=(residual.size * residual.dtype.itemsize
                            if has_residual else 0),
        ),
    )(*args)
