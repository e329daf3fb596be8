"""Structural vector-sparse ops (pure-JAX path) + dispatch to Pallas kernels.

The jnp path performs *structurally sparse* compute: it multiplies only the
stored tiles, so compiled HLO FLOPs drop with density exactly as the paper's
cycle count does.  It is fully GSPMD-partitionable (the strip axis NB shards
over the tensor-model axis) and scan-over-layers compatible (static S).

impl:
  'jnp'          — structural gather + batched matmul (works everywhere,
                   shardable)
  'pallas'       — `repro.kernels` TPU kernel (interpret=True on CPU); for
                   convs this is the halo-blocked direct-input layout
                   ('pallas-halo' is an explicit alias)
  'pallas-stack' — the conv kernel on the materialized row-tap stack
                   (oracle/fallback layout; ~kh*stride x the HBM traffic)
  'auto'         — pallas (halo) on TPU backends, jnp otherwise

`models.graph.apply_sparse_conv` runs a dense narrow-Cin stem as one XLA
dot over its `s2d_im2col` patches before any of these, on every impl but
'jnp' and 'pallas-stack'.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from .vector_sparse import VectorSparse

__all__ = [
    "vs_matmul", "im2col", "im2col_3x3", "s2d_im2col", "s2d_weight_matrix",
    "vs_conv2d", "vs_conv2d_3x3",
    "dense_conv2d", "dense_conv2d_3x3", "conv_weight_to_matrix", "same_pads",
]


def same_pads(size: int, k: int, stride: int,
              dilation: int = 1) -> tuple[int, int, int]:
    """XLA-"SAME" geometry: (out_size, pad_low, pad_high).

    ``dilation`` spaces the kernel taps ``dilation`` elements apart, so the
    effective kernel extent is ``(k - 1) * dilation + 1`` — exactly XLA's
    ``rhs_dilation`` SAME accounting.
    """
    out = -(-size // stride)
    ke = (k - 1) * dilation + 1
    total = max((out - 1) * stride + ke - size, 0)
    lo = total // 2
    return out, lo, total - lo


def _use_pallas(impl: str) -> bool:
    if impl.startswith("pallas"):
        return True
    if impl == "jnp":
        return False
    from repro.kernels import ops as kops  # lazy: avoid import cycle

    # 'auto': the compiled kernels wherever they need no interpreter
    return not kops._interpret()


def _conv_impl(impl: str) -> str:
    """Map the public impl string to the conv kernel layout."""
    return "stack" if impl == "pallas-stack" else "halo"


def vs_matmul(
    x: jax.Array,
    vs: VectorSparse,
    *,
    bias: jax.Array | None = None,
    residual: jax.Array | None = None,
    scale: jax.Array | None = None,
    fuse_relu: bool = False,
    impl: str = "jnp",
    out_dtype: Any = None,
    skip_zero_inputs: bool = True,
) -> jax.Array:
    """x (..., K) @ sparse W (K, N) -> (..., N).

    FLOPs = density * dense FLOPs (structural skip of zero weight vectors —
    the paper's weight-side zero skipping).  ``skip_zero_inputs`` additionally
    skips dynamically-zero activation vectors in the Pallas path (the paper's
    input-side skipping; the jnp path cannot skip dynamically under XLA's
    static schedules, matching a dense-issue accelerator).  ``bias`` (N,),
    ``residual`` (..., N) and ``fuse_relu`` run the epilogue fused in the
    Pallas kernel and in f32 before the output cast in the jnp path
    (residual added before the ReLU — the ResNet shortcut).

    INT8 (int8 ``x`` + int8 ``vs.vals`` + ``scale`` (N,)): each sparse step
    multiply-accumulates in int32 (exact) and enters the shared f32
    accumulator — per-step sums stay < 2^24 so the jnp path is bit-exact
    against the Pallas kernel — and the epilogue dequantizes first:
    acc -> *scale -> +bias -> +residual -> max(0).  Output defaults to f32.
    """
    out_dtype = out_dtype or (jnp.float32 if x.dtype == jnp.int8 else x.dtype)
    *batch, k = x.shape
    assert k == vs.shape[0], (x.shape, vs.shape)
    if _use_pallas(impl):
        from repro.kernels import ops as kops  # lazy: avoid import cycle

        x2 = x.reshape(-1, k)
        res2 = (residual.reshape(-1, vs.shape[1])
                if residual is not None else None)
        out = kops.vsmm(x2, vs, bias=bias, residual=res2, scale=scale,
                        fuse_relu=fuse_relu,
                        skip_zero_inputs=skip_zero_inputs)
        return out.reshape(*batch, vs.shape[1]).astype(out_dtype)

    nb, s, vk, vn = vs.vals.shape
    kb = k // vk
    x2 = x.reshape(-1, kb, vk)  # (M, KB, vk)
    int8 = x2.dtype == jnp.int8

    def step(acc: jax.Array, sv: tuple[jax.Array, jax.Array]
             ) -> tuple[jax.Array, None]:
        idx_s, w_s = sv  # (NB,), (NB, vk, vn)
        xg = jnp.take(x2, idx_s, axis=1)  # (M, NB, vk)
        if int8:
            part = jnp.einsum(
                "mjk,jkn->mjn", xg, w_s, preferred_element_type=jnp.int32
            ).astype(jnp.float32)
        else:
            part = jnp.einsum(
                "mjk,jkn->mjn", xg, w_s, preferred_element_type=jnp.float32
            )
        return acc + part, None

    acc0 = jnp.zeros((x2.shape[0], nb, vn), jnp.float32)
    acc, _ = jax.lax.scan(step, acc0, (vs.idx.T, vs.vals.transpose(1, 0, 2, 3)))
    y = acc.reshape(*batch, nb * vn)
    if scale is not None:
        # scales are powers of two (see `models.graph.weight_scales`), so
        # this multiply is exact — FMA contraction by the compiler cannot
        # change the result and parity with the Pallas kernels stays
        # bit-exact under any fusion decisions
        y = y * scale.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    if residual is not None:
        y = y + residual.astype(jnp.float32)
    if fuse_relu:
        y = jnp.maximum(y, 0.0)
    return y.astype(out_dtype)


def im2col(
    x: jax.Array, *, kh: int = 3, kw: int = 3, stride: int = 1,
    dilation: int = 1,
) -> jax.Array:
    """NHWC, SAME padding -> (N, Hout, Wout, kh*kw*C) patches, (ky, kx)
    row-major — the layout `conv_weight_to_matrix` flattens weights into.
    ``dilation`` spaces the taps: tap (ky, kx) reads the padded input at
    (ky*dilation + stride*i, kx*dilation + stride*j)."""
    n, h, w, c = x.shape
    ho, pt, pb = same_pads(h, kh, stride, dilation)
    wo, pl_, pr = same_pads(w, kw, stride, dilation)
    xp = jnp.pad(x, ((0, 0), (pt, pb), (pl_, pr), (0, 0)))
    cols = [
        jax.lax.slice(
            xp,
            (0, ky * dilation, kx * dilation, 0),
            (n, ky * dilation + stride * (ho - 1) + 1,
             kx * dilation + stride * (wo - 1) + 1, c),
            (1, stride, stride, 1),
        )
        for ky in range(kh)
        for kx in range(kw)
    ]
    return jnp.concatenate(cols, axis=-1)


def _s2d_taps(k: int, stride: int, dilation: int) -> int:
    """Block offsets per axis of a k-tap axis read through space-to-depth
    by ``stride``: tap t lands at block t*dilation // stride."""
    return (k - 1) * dilation // stride + 1


def s2d_im2col(
    x: jax.Array, *, kh: int = 3, kw: int = 3, stride: int = 1,
    dilation: int = 1,
) -> jax.Array:
    """NHWC, SAME padding -> (N, Hout, Wout, KY*KX*stride²*C) patches of
    the stride x stride space-to-depth of the padded input: the stride
    becomes channels, so each of the KY*KX (`_s2d_taps`) block offsets is
    one unit-stride slice.  Pair with `s2d_weight_matrix`; at stride 1
    and dilation 1 this is `im2col`.  On a TPU a strided slice of a
    narrow-C map costs about as much as the whole stem, the space-to-depth
    one relayout."""
    n, h, w, c = x.shape
    s = stride
    ho, pt, _ = same_pads(h, kh, s, dilation)
    wo, pl_, _ = same_pads(w, kw, s, dilation)
    ty, tx = _s2d_taps(kh, s, dilation), _s2d_taps(kw, s, dilation)
    qh, qw = ho + ty - 1, wo + tx - 1
    xp = jnp.pad(x, ((0, 0), (pt, max(s * qh - pt - h, 0)),
                     (pl_, max(s * qw - pl_ - w, 0)), (0, 0)))
    xs = xp[:, :s * qh, :s * qw].reshape(n, qh, s, qw, s, c)
    xs = xs.transpose(0, 1, 3, 2, 4, 5).reshape(n, qh, qw, s * s * c)
    return jnp.concatenate(
        [xs[:, qy:qy + ho, qx:qx + wo] for qy in range(ty)
         for qx in range(tx)], axis=-1)


def s2d_weight_matrix(w: np.ndarray, *, stride: int = 1,
                      dilation: int = 1) -> np.ndarray:
    """(kh, kw, C, Cout) -> the (KY*KX*stride²*C, Cout) matrix that
    `s2d_im2col` patches multiply: tap (ky, kx) sits at block
    (ky*dilation // stride, kx*dilation // stride), phase
    (ky*dilation % stride, kx*dilation % stride); rows no tap reaches are
    zero."""
    kh, kw, c, cout = w.shape
    ty, tx = _s2d_taps(kh, stride, dilation), _s2d_taps(kw, stride, dilation)
    m = np.zeros((ty, tx, stride, stride, c, cout), w.dtype)
    for ky in range(kh):
        for kx in range(kw):
            qy, py = divmod(ky * dilation, stride)
            qx, px = divmod(kx * dilation, stride)
            m[qy, qx, py, px] = w[ky, kx]
    return m.reshape(-1, cout)


def im2col_3x3(x: jax.Array) -> jax.Array:
    """3x3/s1/p1 patches (back-compat alias)."""
    return im2col(x, kh=3, kw=3, stride=1)


def _vs_conv2d_depthwise_jnp(
    x: jax.Array, w_vs: VectorSparse, *, kh: int, kw: int, stride: int,
    dilation: int,
) -> jax.Array:
    """Structural depthwise conv: the sparse weight is (kh*kw, C) — one row
    per tap, strips over ``vc``-channel tiles, ``idx[j, s]`` the tap id of
    the s-th stored tap-vector of channel tile j.  The scan multiplies only
    the stored (tap, channel-tile) vectors — elementwise VPU work, the
    per-channel analogue of the weight-side structural skip."""
    n, h, w, c = x.shape
    vc = w_vs.vn
    assert w_vs.vk == 1 and w_vs.shape == (kh * kw, c), (w_vs.shape, kh, kw, c)
    p = im2col(x, kh=kh, kw=kw, stride=stride, dilation=dilation)
    _, ho, wo, _ = p.shape
    p4 = p.reshape(n * ho * wo, kh * kw, c // vc, vc)

    def step(acc: jax.Array, sv: tuple[jax.Array, jax.Array]
             ) -> tuple[jax.Array, None]:
        idx_s, w_s = sv  # (NB,), (NB, 1, vc)
        xg = jnp.take_along_axis(p4, idx_s[None, None, :, None], axis=1)[:, 0]
        return acc + xg.astype(jnp.float32) * w_s[:, 0].astype(jnp.float32), None

    acc0 = jnp.zeros((p4.shape[0], c // vc, vc), jnp.float32)
    acc, _ = jax.lax.scan(
        step, acc0, (w_vs.idx.T, w_vs.vals.transpose(1, 0, 2, 3)))
    return acc.reshape(n, ho, wo, c)


def _vs_conv2d_grouped_jnp(
    x: jax.Array, w_vs: VectorSparse, *, kh: int, kw: int, stride: int,
    groups: int, dilation: int,
) -> jax.Array:
    """Structural grouped conv: the sparse weight is (kh*kw*Cin/G, Cout)
    with strips group-major (strip j belongs to group j // (strips/G) and
    its K-tiles index that group's channels only).  Each group is one
    `vs_matmul` over its channel slice of the im2col patches."""
    c = x.shape[-1]
    cin_g = c // groups
    spg = w_vs.n_strips // groups
    assert w_vs.n_strips % groups == 0, (w_vs.n_strips, groups)
    if kh == 1 and kw == 1:
        patches = x[:, ::stride, ::stride] if stride != 1 else x
    else:
        patches = im2col(x, kh=kh, kw=kw, stride=stride, dilation=dilation)
    *batch, _ = patches.shape
    pg = patches.reshape(*batch, kh * kw, groups, cin_g)
    outs = []
    for g in range(groups):
        sub = VectorSparse(
            vals=w_vs.vals[g * spg:(g + 1) * spg],
            idx=w_vs.idx[g * spg:(g + 1) * spg],
            shape=(kh * kw * cin_g, spg * w_vs.vn),
        )
        outs.append(vs_matmul(
            pg[..., g, :].reshape(*batch, kh * kw * cin_g), sub,
            impl="jnp", out_dtype=jnp.float32))
    return jnp.concatenate(outs, axis=-1)


def vs_conv2d(
    x: jax.Array,
    w_vs: VectorSparse,
    *,
    kh: int = 3,
    kw: int = 3,
    stride: int = 1,
    groups: int = 1,
    dilation: int = 1,
    bias: jax.Array | None = None,
    residual: jax.Array | None = None,
    scale: jax.Array | None = None,
    fuse_relu: bool = False,
    impl: str = "jnp",
) -> jax.Array:
    """kh x kw / stride / dilation / SAME conv with vector-sparse weights,
    optionally grouped.

    Weight matrix layout: (kh*kw*Cin/groups, Cout) with K ordered
    (ky, kx, cin-within-group) and output strips group-major — a zero K-tile
    is a pruned run of input channels for one kernel position, the TPU
    analogue of the paper's pruned kernel columns.  Depthwise
    (groups == Cin, multiplier 1) degenerates to a (kh*kw, C) tap matrix
    with vk == 1: strips are ``vn``-channel tiles and each stored vector is
    one tap's weights across the tile.  1x1 ungrouped convs are the sparse
    matmul over pixels (stride subsamples first).  On the Pallas path
    ``impl="pallas"``/``"pallas-halo"`` runs the halo-blocked direct-input
    kernels (~1x-input HBM traffic) and ``impl="pallas-stack"`` the
    materialized row-tap stack oracle.  ``bias``,
    ``residual`` (the output-shaped ResNet shortcut, added before the ReLU)
    and ``fuse_relu`` run the epilogue fused in the Pallas path and in f32
    before the output cast in the jnp path — bit-identical math either way.

    INT8 (int8 ``x`` + int8 ``w_vs.vals`` + ``scale`` (Cout,)): the MAC runs
    exactly (int32 accumulation into the shared f32 accumulator) and the
    epilogue dequantizes first — acc -> *scale -> +bias -> +residual (f32)
    -> max(0) — with f32 output.
    """
    if _use_pallas(impl):
        from repro.kernels import ops as kops  # lazy: avoid import cycle

        return kops.vsconv(
            x, w_vs, kh=kh, kw=kw, stride=stride, groups=groups,
            dilation=dilation, bias=bias, residual=residual, scale=scale,
            fuse_relu=fuse_relu, impl=_conv_impl(impl),
        )
    if groups == 1:
        if kh == 1 and kw == 1:
            patches = x[:, ::stride, ::stride] if stride != 1 else x
        else:
            patches = im2col(x, kh=kh, kw=kw, stride=stride,
                             dilation=dilation)
        y = vs_matmul(patches, w_vs, impl="jnp", out_dtype=jnp.float32)
    elif groups == x.shape[-1] and w_vs.shape == (kh * kw, x.shape[-1]):
        # multiplier-1 depthwise; a channel-multiplier conv (cout > cin)
        # falls through to the general grouped path with vk == 1
        y = _vs_conv2d_depthwise_jnp(x, w_vs, kh=kh, kw=kw, stride=stride,
                                     dilation=dilation)
    else:
        y = _vs_conv2d_grouped_jnp(x, w_vs, kh=kh, kw=kw, stride=stride,
                                   groups=groups, dilation=dilation)
    if scale is not None:
        # exact multiply: scales are powers of two (see
        # `models.graph.weight_scales`) — FMA-contraction-proof
        y = y * scale.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    if residual is not None:
        y = y + residual.astype(jnp.float32)
    if fuse_relu:
        y = jnp.maximum(y, 0.0)
    return y.astype(jnp.float32 if x.dtype == jnp.int8 else x.dtype)


def vs_conv2d_3x3(x: jax.Array, w_vs: VectorSparse, *, impl: str = "jnp") -> jax.Array:
    """3x3/s1/p1 conv with vector-sparse weights (back-compat alias)."""
    return vs_conv2d(x, w_vs, kh=3, kw=3, stride=1, impl=impl)


def dense_conv2d(x: jax.Array, w: jax.Array, *, stride: int = 1,
                 groups: int = 1, dilation: int = 1) -> jax.Array:
    """Dense oracle: w is (kh, kw, Cin/groups, Cout), SAME padding."""
    return jax.lax.conv_general_dilated(
        x,
        w,
        window_strides=(stride, stride),
        padding="SAME",
        rhs_dilation=(dilation, dilation),
        feature_group_count=groups,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def dense_conv2d_3x3(x: jax.Array, w: jax.Array) -> jax.Array:
    """Dense 3x3/s1 oracle (back-compat alias)."""
    return dense_conv2d(x, w, stride=1)


def conv_weight_to_matrix(w: jax.Array) -> jax.Array:
    """(kh,kw,Cin,Cout) -> (kh*kw*Cin, Cout) in the im2col (ky,kx,cin) order."""
    kh, kw, cin, cout = w.shape
    return w.reshape(kh * kw * cin, cout)
