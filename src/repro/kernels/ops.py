"""Public jit'd wrappers around the Pallas kernels.

Handle padding to kernel-friendly shapes, backend dispatch (interpret=True on
CPU so kernels validate everywhere, compiled on real TPU), and layout prep
(the halo direct input / the row-tap stack).

`vsconv` covers the generalized kernel family:

  vsconv(x, vs, kh=3, kw=3, stride=1, groups=1, dilation=1, bias=None,
         fuse_relu=False, impl="halo")

  * arbitrary odd/even kh x kw taps, SAME padding for the given stride
    (Hout = ceil(H/stride)) — the weight matrix is (kh*kw*Cin/groups, Cout)
    with K ordered (ky, kx, cin), i.e. `core.sparse_ops.conv_weight_to_matrix`;
  * stride 1 and 2 (any stride the tap decomposition supports, in fact),
    dilated taps (effective extent (k-1)*dilation + 1), grouped convs
    (strips group-major, per-group cin tiles), and depthwise
    (groups == Cin) via the per-channel tap kernels;
  * ungrouped 1x1 convs route through `vsmm` over flattened pixels (a
    pointwise conv *is* the sparse matmul; stride subsamples first) —
    ResNet projections and MobileNet pointwise stages;
  * ``impl`` picks the input layout: ``"halo"`` (default) reads the raw
    SAME-padded input through overlapping halo blocks and resolves the tap
    in-kernel — ~1x-input HBM traffic; ``"stack"`` materializes the
    kh*stride-plane row-tap stack first — the bandwidth-dumb oracle and
    fallback layout;
  * ``bias``/``fuse_relu`` run the epilogue inside the kernel, so the
    post-ReLU zeros feeding the next layer's input-side skip are produced
    on-chip for free.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.vector_sparse import VectorSparse
from .vsmm import build_vsmm_input, vsmm_pallas
from .vsconv import (
    vsconv_pallas, vsconv_halo_pallas, vsconv_dw_halo_pallas,
    vsconv_dw_stack_pallas, build_row_tap_stack, build_halo_input,
    same_pads,
)

__all__ = ["vsmm", "vsconv"]


def _interpret() -> bool:
    """True off the TPU: the kernels then run in interpret mode, and
    ``impl="auto"`` (`core.sparse_ops`) takes the jnp path instead.  The
    one place the backend decides how the sparse path runs."""
    return jax.default_backend() != "tpu"


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def vsmm(
    x: jax.Array,
    vs: VectorSparse,
    *,
    bm: int = 256,
    bias: jax.Array | None = None,
    residual: jax.Array | None = None,
    scale: jax.Array | None = None,
    skip_zero_inputs: bool = True,
    fuse_relu: bool = False,
    interpret: bool | None = None,
) -> jax.Array:
    """x (M, K) @ vector-sparse W (K, N) -> (M, N); pads M to a bm multiple.

    Optional fused epilogue: ``scale`` (N,) int8 dequant multiply + ``bias``
    (N,) add + ``residual`` (M, N) add (before the ReLU — the ResNet
    shortcut) + ``fuse_relu`` inside the kernel (f32 accumulator, one cast
    at flush).
    """
    m = x.shape[0]
    interpret = _interpret() if interpret is None else interpret
    bm = min(bm, _round_up(m, 8))
    mp = _round_up(m, bm)
    if mp != m:
        x = jnp.pad(x, ((0, mp - m), (0, 0)))
        if residual is not None:
            residual = jnp.pad(residual, ((0, mp - m), (0, 0)))
    out = vsmm_pallas(
        build_vsmm_input(x, vs.vk), vs, bm=bm, bias=bias, residual=residual,
        scale=scale,
        skip_zero_inputs=skip_zero_inputs,
        fuse_relu=fuse_relu, interpret=interpret
    )
    return out[:m] if mp != m else out


def vsconv(
    x: jax.Array,
    vs: VectorSparse,
    *,
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
    impl: str = "halo",
    interpret: bool | None = None,
) -> jax.Array:
    """NHWC kh x kw / stride / dilation / SAME (grouped) conv with
    vector-sparse (kh*kw*Cin/groups, Cout) weights
    -> (N, ceil(H/stride), ceil(W/stride), Cout).

    Ungrouped 1x1 convs dispatch to the sparse matmul over flattened pixels
    (stride subsamples first); depthwise convs (groups == Cin, multiplier
    1, weight matrix (kh*kw, C) encoded vk=1) run the per-channel tap
    kernels; everything else runs one of the two direct tap-decomposed
    Pallas kernels — grouped convs shard the cin-tile axis per group.
    ``impl="halo"`` (default — raw input, halo-blocked, tap resolved
    in-kernel) or ``impl="stack"`` (the materialized row-tap/phase stack,
    kept as oracle and fallback) selects the input layout for all of them.
    ``bias`` (Cout,), ``residual`` (the output-shaped ResNet shortcut,
    added before the ReLU) and ``fuse_relu`` fuse the epilogue in-kernel.

    Every geometry runs here, the padded small-Cin stem included; on the
    served path `models.graph.apply_sparse_conv` runs a float stem that
    `sparsify` kept dense as one XLA dot instead, so only int8 stems and the
    oracle impls reach this function with one.
    """
    n, h, w, c = x.shape
    interpret = _interpret() if interpret is None else interpret
    if impl not in ("halo", "stack"):
        raise ValueError(f"vsconv impl must be 'halo' or 'stack', got {impl!r}")
    assert c % groups == 0, (c, groups)
    # multiplier-1 depthwise only; a channel-multiplier conv (cout > cin)
    # still runs the general grouped kernels with vk == cin/groups == 1
    depthwise = groups > 1 and groups == c and vs.shape == (kh * kw, c)
    if kh == 1 and kw == 1 and groups == 1:
        if stride != 1:
            x = x[:, ::stride, ::stride]
        _, ho, wo, _ = x.shape
        res2 = (residual.reshape(n * ho * wo, -1)
                if residual is not None else None)
        out = vsmm(
            x.reshape(-1, c), vs, bias=bias, residual=res2, scale=scale,
            skip_zero_inputs=skip_zero_inputs, fuse_relu=fuse_relu,
            interpret=interpret,
        )
        return out.reshape(n, ho, wo, -1)
    ho, _, _ = same_pads(h, kh, stride, dilation)
    wo, _, _ = same_pads(w, kw, stride, dilation)
    bh = min(bh, ho)
    hop = _round_up(ho, bh)
    if residual is not None and hop != ho:
        residual = jnp.pad(residual, ((0, 0), (0, hop - ho), (0, 0), (0, 0)))
    common = dict(
        w_out=wo, kh=kh, kw=kw, stride=stride, dilation=dilation, bias=bias,
        residual=residual, scale=scale, bh=bh,
        skip_zero_inputs=skip_zero_inputs,
        fuse_relu=fuse_relu, interpret=interpret,
    )
    if depthwise:
        # per-channel tap kernels: strips are vn-channel tiles (vk == 1)
        assert vs.vk == 1 and vs.shape == (kh * kw, c), (vs.shape, kh, kw, c)
        if impl == "halo":
            xh = build_halo_input(x, kh=kh, kw=kw, stride=stride,
                                  dilation=dilation, vk=vs.vn, h_out=hop)
            out = vsconv_dw_halo_pallas(xh, vs, **common)
        else:
            xt = build_row_tap_stack(x, kh=kh, kw=kw, stride=stride,
                                     dilation=dilation, h_out=hop)
            out = vsconv_dw_stack_pallas(xt, vs, **common)
    elif impl == "halo":
        xh = build_halo_input(x, kh=kh, kw=kw, stride=stride,
                              dilation=dilation, vk=vs.vk, h_out=hop)
        out = vsconv_halo_pallas(xh, vs, groups=groups, **common)
    else:
        xt = build_row_tap_stack(x, kh=kh, kw=kw, stride=stride,
                                 dilation=dilation, h_out=hop)
        out = vsconv_pallas(xt, vs, groups=groups, **common)
    return out[:, :ho] if hop != ho else out
