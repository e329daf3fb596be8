"""Network IR + graph executor: whole networks on the vector-sparse datapath.

VSCNN's claim is that *one* vector-sparse datapath serves whole networks.
This module is the model-side half of that claim: instead of a hand-written
apply function per network, a network is data — a `SparseNet` holding a flat
tuple of `LayerSpec`s — and one walker (`net_apply`) runs any of them dense
or sparse, with one generic `sparsify` that vector-prunes every conv and FC
layer (BN folded into the conv weights/bias first, so batch-norm costs
nothing at inference).

LayerSpec vocabulary
--------------------
  Conv(name, cin, cout, kh, kw, stride, bn, relu, residual, src, dst)
      kh x kw / stride / SAME conv.  ``bn=True`` gives the layer inference
      batch-norm parameters (scale/offset/mean/var) instead of a bias; at
      sparsify time BN is folded into the weights and a bias, so the sparse
      path never sees it.  ``residual`` names a saved slot whose tensor is
      added *before* the ReLU — on the sparse path this rides the kernels'
      fused epilogue (one extra VMEM read, no extra HBM round trip).
      ``src`` reads the layer input from a saved slot instead of the stream
      and ``dst`` writes the output to a slot without touching the stream —
      together they express shortcut branches (the ResNet downsample
      projection) without a general DAG.
  FC(name, din, dout, relu)      dense/sparse fully-connected (+bias, ReLU).
  Classifier(name, din, dout)    FC with relu=False — the logits head.
  Pool(kind, size, stride, padding)   'max' | 'avg' window pool or 'gap'
      (global average pool, the ResNet head).
  ResidualAdd(key, relu)         explicit unfused shortcut add (for graphs
      whose producer layer can't absorb it; builders prefer the fused
      Conv(residual=...) form).
  Save(key)                      checkpoint the stream into a named slot.
  Flatten()                      NHWC -> (N, features).

Adding a new network = writing a builder that returns a `SparseNet` (see
`build_vgg16` / `build_resnet18`); schema, forward, sparsification, traffic
collection and the accelerator cycle model all come for free from the
walker.

Sparse layer specs
------------------
`sparsify(net, params, density)` returns ``(sparse, pruned)``: a dict
mapping layer name -> `SparseConv` / `SparseFC` (balanced block-CSR weights
+ geometry + folded bias), and a pruned *dense* param tree computing the
identical function (BN folded, remainders intact) for oracle comparison.
FC layers whose Cout doesn't tile (e.g. a 1000-class head) are zero-padded
to the strip width and the padded columns are sliced off after the kernel —
the remainder strip, so every FC runs sparse.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.diagnostics import Diagnostic, VSCheckError
from repro.core import (
    VectorSparse,
    conv_cin_major,
    from_mask,
    prune_vectors_balanced,
    vs_matmul,
    vs_conv2d,
    dense_conv2d,
    s2d_im2col,
    s2d_weight_matrix,
)
from .layers import P

__all__ = [
    "Conv", "FC", "Classifier", "Pool", "ResidualAdd", "Save", "Flatten",
    "SparseNet", "SparseConv", "SparseFC", "BatchedApply", "shard_sparse",
    "ConvTileGeometry", "FCTileGeometry", "conv_tile_geometry",
    "fc_tile_geometry", "keeps_dense", "strip_steps",
    "sparse_conv_from_dense", "ORACLE_IMPLS", "runs_xla_conv",
    "is_depthwise_conv",
    "apply_sparse_conv", "apply_sparse_fc",
    "weight_scales", "quantize_weights_int8", "quantize_activations_int8",
    "net_schema", "net_apply", "sparsify", "collect_conv_traffic",
    "build_vgg16", "build_resnet18", "build_resnet34", "build_resnet50",
    "build_mobilenet_v1", "build_resnet_stem",
    "VGG16_LAYERS", "RESNET18_STAGES", "RESNET34_STAGES", "RESNET50_STAGES",
    "MOBILENET_V1_PLAN", "BN_EPS",
]

BN_EPS = 1e-5


# --------------------------------------------------------------------------
# Layer specs (the IR)
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Conv:
    """kh x kw / stride / dilation / SAME (grouped) conv (+BN) (+residual)
    (+ReLU).

    ``groups`` shards the channels: the weight is XLA's grouped HWIO
    (kh, kw, cin/groups, cout) and output block g reads input group g only.
    ``groups == cin`` is a depthwise conv (multiplier 1, cout == cin) —
    routed through the per-channel tap kernels on the sparse path.
    ``dilation`` spaces the taps (effective extent (k-1)*dilation + 1).
    """

    name: str
    cin: int
    cout: int
    kh: int = 3
    kw: int = 3
    stride: int = 1
    groups: int = 1
    dilation: int = 1
    bn: bool = False
    relu: bool = True
    residual: str | None = None  # slot added before ReLU (fused epilogue)
    src: str | None = None       # read input from slot, not the stream
    dst: str | None = None       # write output to slot, leave stream as-is
    # a depthwise conv with channel multiplier > 1 (groups == cin,
    # cout == m*cin) has no per-channel tap encoding; it can only run the
    # general grouped kernels with vk == 1 — correct but MXU-wasteful.
    # `sparsify`/vscheck refuse it (rule VSC109) unless explicitly allowed.
    allow_fallback: bool = False


@dataclasses.dataclass(frozen=True)
class FC:
    """Fully-connected layer: x @ W + b (+ReLU)."""

    name: str
    din: int
    dout: int
    relu: bool = True


def Classifier(name: str, din: int, dout: int) -> FC:
    """The logits head: an FC without the ReLU."""
    return FC(name, din, dout, relu=False)


@dataclasses.dataclass(frozen=True)
class Pool:
    """'max' | 'avg' window pool, or 'gap' (global average pool)."""

    kind: str = "max"
    size: int = 2
    stride: int | None = None  # None -> size
    padding: str = "VALID"


@dataclasses.dataclass(frozen=True)
class ResidualAdd:
    """Explicit (unfused) shortcut add: x = [relu](x + saved[key])."""

    key: str
    relu: bool = True


@dataclasses.dataclass(frozen=True)
class Save:
    """Checkpoint the stream into a named slot."""

    key: str


@dataclasses.dataclass(frozen=True)
class Flatten:
    """NHWC -> (N, features)."""


@dataclasses.dataclass(frozen=True)
class SparseNet:
    """A network as data: a name and a flat tuple of LayerSpecs."""

    name: str
    layers: tuple

    def schema(self) -> dict:
        return net_schema(self)

    def apply(self, params: dict, x: jax.Array, *,
              sparse: dict | None = None, impl: str = "auto",
              collect: list | None = None) -> jax.Array:
        return net_apply(self, params, x, sparse=sparse, impl=impl,
                         collect=collect)

    def sparsify(self, params: dict, density: float, *, vk: int = 32,
                 vn: int = 128, include_fc: bool = True,
                 dtype: Any = None) -> tuple[dict, dict]:
        return sparsify(self, params, density, vk=vk, vn=vn,
                        include_fc=include_fc, dtype=dtype)

    def batched_apply(self, params: dict, *,
                      sparse: dict | None = None, impl: str = "auto",
                      key: tuple = (), cache: dict | None = None
                      ) -> "BatchedApply":
        """Serving entry point: jit-compiled apply with a compile cache
        keyed on (net, weight-set key, impl, batch bucket)."""
        return BatchedApply(self, params, sparse=sparse, impl=impl, key=key,
                            cache=cache if cache is not None else {})

    def conv_layers(self) -> list[Conv]:
        return [l for l in self.layers if isinstance(l, Conv)]

    def fc_layers(self) -> list[FC]:
        return [l for l in self.layers if isinstance(l, FC)]


# --------------------------------------------------------------------------
# Sparse layer entries (what `sparsify` produces, what the walker consumes)
# --------------------------------------------------------------------------

@dataclasses.dataclass
class SparseConv:
    """One vector-sparse conv layer: weights + geometry.

    ``cin_pad`` zero channels are appended to the input before an encoded
    path runs the conv — how a non-tileable Cin (e.g. the 3-channel stem)
    becomes a multiple of the K-tile length.  The padded weight rows are
    zero, so the math is unchanged.  ``dense_w`` (set by `sparsify` on a
    float layer it keeps dense, see `keeps_dense`) is the folded weight
    without the pad, laid out as `core.sparse_ops.s2d_weight_matrix`:
    `apply_sparse_conv` then runs the layer as one XLA dot over the
    unpadded input's `s2d_im2col` patches, and only the oracle impls read
    ``vs``.  ``groups``/``dilation`` carry
    the grouped/dilated geometry (``groups == cin`` is depthwise: the
    encoded matrix is the (kh*kw, C) tap matrix with vk == 1).  ``bias``
    (when set) overrides the param-tree bias — this is where the BN-folded
    bias lives.  ``scale`` (set iff the weights are int8-quantized) holds
    the per-cout symmetric dequant scales; the walker quantizes the layer
    input per-tensor and hands the combined scale to the kernel epilogue.
    """

    vs: VectorSparse
    kh: int = 3
    kw: int = 3
    stride: int = 1
    groups: int = 1
    dilation: int = 1
    cin_pad: int = 0
    bias: jax.Array | None = None
    scale: jax.Array | None = None
    dense_w: jax.Array | None = None


@dataclasses.dataclass
class SparseFC:
    """One vector-sparse FC layer.

    ``dout`` is the true output width; the encoded matrix may be zero-padded
    to a strip multiple (the remainder strip for non-tileable heads, e.g.
    1000 classes) — the walker slices the pad columns off after the kernel.
    ``bias`` (when set) overrides the param-tree bias.  ``scale`` (set iff
    the weights are int8-quantized) holds per-cout dequant scales padded to
    the encoded width (pad columns get scale 1.0).
    """

    vs: VectorSparse
    dout: int | None = None
    bias: jax.Array | None = None
    scale: jax.Array | None = None


# --------------------------------------------------------------------------
# Tile geometry (the single source for sparsify AND the static analyzer)
# --------------------------------------------------------------------------

def _largest_divisor(n: int, cap: int) -> int:
    """The largest divisor of ``n`` that is <= ``cap``."""
    d = min(cap, n)
    while n % d:
        d -= 1
    return d


@dataclasses.dataclass(frozen=True)
class ConvTileGeometry:
    """How one conv layer's weights encode into the balanced block-CSR.

    ``vk``/``vn`` are the *encoded* tile dims (possibly shrunk from the
    requested ones), ``cin_pad`` the zero channels appended to the input,
    ``kb`` the stored-tile-id bound per strip (idx values < kb) and ``nb``
    the output-strip count.  `sparse_conv_from_dense` follows exactly this
    geometry; `repro.analysis` re-derives kernel plans from it.
    """

    depthwise: bool
    vk: int
    vn: int
    cin_pad: int
    kb: int
    nb: int


@dataclasses.dataclass(frozen=True)
class FCTileGeometry:
    """FC encoding geometry: ``pad`` zero output columns (the remainder
    strip for non-tileable heads), ``kb`` K-tiles, ``nb`` output strips."""

    vk: int
    vn: int
    pad: int
    kb: int
    nb: int


def conv_tile_geometry(
    kh: int, kw: int, cin_g: int, cout: int, *, vk: int = 32, vn: int = 128,
    groups: int = 1, allow_fallback: bool = False, path: str = "conv",
) -> ConvTileGeometry:
    """Tile geometry of a (kh, kw, cin/groups, cout) conv weight.

    Depthwise (groups == cin, multiplier 1): the (kh*kw, C) per-channel tap
    matrix, vk == 1, strips over channel tiles.  Grouped: K-tiles stay
    inside the group (vk shrinks to a divisor of cin/groups, no padding),
    strips to a divisor of cout/groups.  Ungrouped: channel-pad to a
    multiple of a reduced K-tile when cin doesn't tile.

    A depthwise conv with channel multiplier > 1 (groups > 1, cin_g == 1,
    cout != groups) would fall back to the general grouped kernels with
    vk == 1 — correct but MXU-wasteful (vk-1 dead lanes every issue).
    Raises `VSCheckError` (rule VSC109) unless ``allow_fallback``.
    """
    depthwise = groups > 1 and cin_g == 1 and cout == groups
    if depthwise:
        vn_l = _largest_divisor(cout, vn)
        return ConvTileGeometry(
            depthwise=True, vk=1, vn=vn_l, cin_pad=0, kb=kh * kw,
            nb=cout // vn_l)
    if groups > 1 and cin_g == 1 and not allow_fallback:
        raise VSCheckError(Diagnostic(
            "VSC109", "error", path,
            f"depthwise channel-multiplier {cout // groups} > 1 "
            f"(groups={groups}, cout={cout}) has no per-channel tap "
            f"encoding and would run grouped kernels with vk == 1",
            hint="set Conv(allow_fallback=True) to accept the vk==1 "
                 "grouped fallback, or split into depthwise + 1x1",
        ))
    if groups > 1:
        # K-tiles stay inside the group; no channel padding (shrink vk to a
        # divisor of Cin/groups instead — padding would interleave zeros
        # into every group)
        vk_l = _largest_divisor(cin_g, vk)
        cp = 0
        vn_l = _largest_divisor(cout // groups, vn)
    else:
        if cin_g % vk == 0:
            vk_l, cp = vk, 0
        else:
            vk_l = min(vk, 8)
            cp = -cin_g % vk_l
        vn_l = _largest_divisor(cout, vn)
    return ConvTileGeometry(
        depthwise=False, vk=vk_l, vn=vn_l, cin_pad=cp,
        kb=kh * kw * (cin_g + cp) // vk_l, nb=cout // vn_l)


def fc_tile_geometry(din: int, dout: int, *, vk: int = 32, vn: int = 128
                     ) -> FCTileGeometry | None:
    """FC encoding geometry, or None when the layer stays dense (fan-in not
    a vk multiple — rule VSC116)."""
    if din % vk:
        return None
    vn_l = min(vn, dout)
    pad = -dout % vn_l
    return FCTileGeometry(vk=vk, vn=vn_l, pad=pad, kb=din // vk,
                          nb=(dout + pad) // vn_l)


def keeps_dense(groups: int, cin_g: int, vk: int) -> bool:
    """True for a conv `sparsify` leaves unpruned: ungrouped, with a Cin
    below the requested K-tile (the 3-channel stems).  Vector pruning
    cannot reach it, so every K-tile is stored and its float path is one
    XLA dot (`apply_sparse_conv`).  Grouped and depthwise layers always
    prune: their quota is per strip, i.e. per group."""
    return groups == 1 and cin_g < vk


def strip_steps(kb: int, density: float, *, prune: bool = True) -> int:
    """Stored tiles per strip after balanced pruning — the S grid axis.
    Mirrors `core.pruning.prune_vectors_balanced`'s per-strip quota."""
    if not prune or density >= 1.0:
        return kb
    return max(1, int(round(kb * density)))


# --------------------------------------------------------------------------
# INT8 quantization (compound sparsity x precision)
# --------------------------------------------------------------------------

def _wants_int8(dtype: Any) -> bool:
    """True iff ``dtype`` names int8 (string or dtype-like)."""
    if dtype is None:
        return False
    try:
        return jnp.dtype(dtype) == jnp.dtype(jnp.int8)
    except TypeError:
        return False


def _pow2_up(s: np.ndarray) -> np.ndarray:
    """Round positive scales UP to the next power of two (exactly
    representable in f32).  Po2 scales make every dequant multiply exact —
    scaling an f32 by 2^k only shifts the exponent — so the fused epilogue
    ``acc*s + bias`` is immune to FMA contraction (fma == two-step, bit for
    bit, under any compiler fusion) and matches the shift-based requant of
    fixed-point accelerator datapaths."""
    s64 = np.asarray(s, np.float64)
    p = np.exp2(np.ceil(np.log2(s64)))
    p = np.where(p < s64, p * 2.0, p)  # guard log2 rounding at po2 inputs
    return p.astype(np.float32)


def weight_scales(wm: np.ndarray) -> np.ndarray:
    """Per-cout symmetric int8 scales of a (K, Cout) weight matrix.

    ``s[c] = max|wm[:, c]| / 127`` rounded up to the next power of two (see
    `_pow2_up` — exact dequant multiplies, deterministic epilogue); an
    all-zero column (e.g. a remainder-strip pad column) gets scale 1.0 so
    dequant stays a no-op there.
    """
    s = np.abs(np.asarray(wm, np.float32)).max(axis=0) / 127.0
    return _pow2_up(np.where(s > 0, s, 1.0))


def quantize_weights_int8(wm: np.ndarray,
                          s: np.ndarray) -> np.ndarray:
    """Symmetric round-to-nearest int8 encode of ``wm`` at per-cout scales
    ``s`` (decode is ``wq.astype(f32) * s``, within s/2 of the source)."""
    q = np.rint(np.asarray(wm, np.float32) / s)
    return np.clip(q, -127, 127).astype(np.int8)


def quantize_activations_int8(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-tensor symmetric int8 activation quantization (traceable).

    Returns ``(xq, sx)`` with ``xq = clip(round(x / sx), -127, 127)`` and
    ``sx = max|x| / 127`` rounded up to the next power of two (1.0 when the
    tensor is all-zero, so the encode never divides by zero).  Po2 scales
    keep the combined dequant scale ``sx * s_w`` a power of two, so the
    kernels' epilogue multiply is exact (see `_pow2_up`).
    """
    sx = (jnp.max(jnp.abs(x.astype(jnp.float32))) / 127.0).astype(jnp.float32)
    sx = jnp.where(sx > 0, sx, jnp.float32(1.0))
    p = jnp.exp2(jnp.ceil(jnp.log2(sx))).astype(jnp.float32)
    sx = jnp.where(p < sx, p * jnp.float32(2.0), p)
    xq = jnp.clip(jnp.round(x.astype(jnp.float32) / sx), -127, 127)
    return xq.astype(jnp.int8), sx


def sparse_conv_from_dense(
    w: np.ndarray | jax.Array,
    density: float,
    *,
    vk: int = 32,
    vn: int = 128,
    stride: int = 1,
    groups: int = 1,
    dilation: int = 1,
    prune: bool = True,
    dtype: Any = None,
    allow_fallback: bool = False,
    path: str = "conv",
) -> tuple[SparseConv, np.ndarray]:
    """Dense (kh, kw, Cin/groups, Cout) weight -> (SparseConv, pruned dense
    weight).

    Handles non-tileable Cin by zero-padding channels to a multiple of a
    reduced K-tile length (min(vk, 8)); handles non-tileable Cout by
    shrinking the output strip to the largest divisor of Cout that is <= vn.
    ``prune=False`` (or density >= 1) keeps every tile — the dense network
    in the same format, the paper's single-datapath story.

    Grouped convs (1 < groups < Cin) keep the K axis within the group:
    the matrix is (kh*kw*Cin/groups, Cout), the K-tile length shrinks to a
    divisor of Cin/groups, and the output strip to a divisor of Cout/groups
    so no strip straddles a group boundary — pruning quotas are therefore
    *per group* automatically (each strip scores only its group's weights).
    Depthwise (groups == Cin, multiplier 1) encodes the (kh*kw, Cout) tap
    matrix with vk == 1 and strips over channel tiles — the vectors are
    per-tap channel runs, pruned the same balanced way.
    """
    w = np.asarray(w, np.float32)
    kh, kw, cin_g, cout = w.shape
    int8 = _wants_int8(dtype)
    dtype = jnp.float32 if int8 else (dtype or jnp.float32)
    g = conv_tile_geometry(kh, kw, cin_g, cout, vk=vk, vn=vn, groups=groups,
                           allow_fallback=allow_fallback, path=path)
    vk_l, vn_l, cp = g.vk, g.vn, g.cin_pad
    if g.depthwise:
        # per-channel tap matrix: one row per tap, strips = channel tiles
        wm = w.reshape(kh * kw, cout)
        if prune and density < 1.0:
            wp, mask = prune_vectors_balanced(wm, density, vk_l, vn_l)
        else:
            wp = wm
            mask = np.ones((kh * kw, cout // vn_l), bool)
        scale: np.ndarray | None = None
        if int8:
            # quantize the PRUNED weights: scales see only surviving taps
            scale = weight_scales(wp)
            wq = quantize_weights_int8(wp, scale)
            wp = wq.astype(np.float32) * scale  # dequantized dense oracle
            vs = from_mask(jnp.asarray(wq), mask, vk_l, vn_l)
        else:
            vs = from_mask(jnp.asarray(wp, dtype), mask, vk_l, vn_l)
        spec = SparseConv(vs, kh=kh, kw=kw, stride=stride, groups=groups,
                          dilation=dilation,
                          scale=None if scale is None else jnp.asarray(scale))
        return spec, wp.reshape(kh, kw, 1, cout)
    wpad = np.pad(w, ((0, 0), (0, 0), (0, cp), (0, 0))) if cp else w
    wm = wpad.reshape(kh * kw * (cin_g + cp), cout)
    if prune and density < 1.0:
        wp, mask = prune_vectors_balanced(wm, density, vk_l, vn_l)
    else:
        wp = wm
        mask = np.ones((wm.shape[0] // vk_l, cout // vn_l), bool)
    scale = None
    if int8:
        scale = weight_scales(wp)
        wq = quantize_weights_int8(wp, scale)
        wp = wq.astype(np.float32) * scale  # dequantized dense oracle
        vs = from_mask(jnp.asarray(wq), mask, vk_l, vn_l)
    else:
        vs = from_mask(jnp.asarray(wp, dtype), mask, vk_l, vn_l)
    if kh * kw > 1:
        # cin-major issue order: the halo kernel's input block then revisits
        # (no re-DMA) across consecutive taps of one cin tile — the layout
        # the halo HBM-traffic model assumes.  Order-agnostic everywhere
        # else (the kernels decode each tile id independently).  For a
        # grouped conv the tile ids are group-relative, so the per-group
        # tile count is what orders them.
        vs = conv_cin_major(vs, (cin_g + cp) // vk_l)
    spec = SparseConv(vs, kh=kh, kw=kw, stride=stride, groups=groups,
                      dilation=dilation, cin_pad=cp,
                      scale=None if scale is None else jnp.asarray(scale))
    wp_dense = wp.reshape(kh, kw, cin_g + cp, cout)[:, :, :cin_g]
    return spec, wp_dense


ORACLE_IMPLS = ("jnp", "pallas-stack")


def runs_xla_conv(entry: SparseConv | VectorSparse, impl: str) -> bool:
    """True when `apply_sparse_conv` runs ``entry`` as one XLA dot: a layer
    `sparsify` kept dense in float (``dense_w`` set), on any impl but the
    oracles, which keep their encoded paths."""
    return (isinstance(entry, SparseConv) and entry.dense_w is not None
            and impl not in ORACLE_IMPLS)


def is_depthwise_conv(entry: SparseConv | VectorSparse) -> bool:
    """True for a depthwise conv kept sparse (``groups == cin == cout``,
    its (kh*kw, C) tap matrix encoded with vk == 1), whatever the impl:
    `vs_conv2d` runs it on the per-channel tap kernels, one
    `vsconv_dw_halo_pallas` launch on a TPU."""
    return (isinstance(entry, SparseConv) and entry.dense_w is None
            and entry.groups > 1
            and entry.vs.shape == (entry.kh * entry.kw, entry.groups))


def apply_sparse_conv(x: jax.Array, entry: SparseConv | VectorSparse, *,
                      bias: jax.Array | None = None, fuse_relu: bool = True,
                      residual: jax.Array | None = None,
                      impl: str = "auto") -> jax.Array:
    """Run one conv through the vector-sparse path.

    ``entry`` is a `SparseConv` or a bare `VectorSparse` (legacy 3x3/s1).
    ``residual`` is the output-shaped shortcut added before the ReLU in the
    kernels' fused epilogue.

    A dense narrow-Cin layer (`runs_xla_conv`) is the compiler's job: the
    space-to-depth patches of the unpadded input (`s2d_im2col`, so a
    stride costs no strided slice) times the folded dense weight, one XLA
    dot at ``Precision.HIGHEST``, then the bias, residual and ReLU in f32
    for XLA to fuse.  Its stored tiles hold nothing to skip, and the
    kernel would contract 8-wide K-tiles of 3 real channels, one grid step
    per tap.  A dot, not `lax.conv_general_dilated`: the TPU compiler
    takes 20-30 s over a 3-channel conv at ``HIGHEST``, a few over this
    dot, and every new set of weights compiles again.

    An int8 entry (``spec.scale`` set) quantizes the layer input per-tensor
    first; the kernel accumulates int8 x int8 in int32 and the combined
    scale ``sx * s_w`` dequantizes in the fused epilogue (before bias).
    """
    spec = entry if isinstance(entry, SparseConv) else SparseConv(entry)
    if runs_xla_conv(spec, impl):
        patches = s2d_im2col(x, kh=spec.kh, kw=spec.kw, stride=spec.stride,
                             dilation=spec.dilation)
        y = jnp.dot(patches, spec.dense_w,
                    precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32)
        if bias is not None:
            y = y + bias.astype(jnp.float32)
        if residual is not None:
            y = y + residual.astype(jnp.float32)
        if fuse_relu:
            y = jnp.maximum(y, 0.0)
        return y.astype(x.dtype)
    scale = spec.scale
    if scale is not None:
        x, sx = quantize_activations_int8(x)
        scale = sx * scale
    if spec.cin_pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, spec.cin_pad)))
    return vs_conv2d(
        x, spec.vs, kh=spec.kh, kw=spec.kw, stride=spec.stride,
        groups=spec.groups, dilation=spec.dilation, bias=bias,
        residual=residual, fuse_relu=fuse_relu, impl=impl, scale=scale,
    )


def apply_sparse_fc(x: jax.Array, entry: SparseFC | VectorSparse, *,
                    bias: jax.Array | None = None, fuse_relu: bool = False,
                    residual: jax.Array | None = None,
                    impl: str = "auto") -> jax.Array:
    """Run one FC layer through the vector-sparse path.

    ``entry`` is a `SparseFC` or a bare `VectorSparse`.  The encoded matrix
    may carry remainder-strip zero columns; bias/residual are padded to the
    encoded width and the pad columns sliced off after the kernel.
    """
    spec = entry if isinstance(entry, SparseFC) else SparseFC(entry)
    n_enc = spec.vs.shape[1]
    dout = spec.dout or n_enc
    if bias is not None and bias.shape[-1] != n_enc:
        bias = jnp.pad(bias, (0, n_enc - bias.shape[-1]))
    if residual is not None and residual.shape[-1] != n_enc:
        residual = jnp.pad(
            residual,
            [(0, 0)] * (residual.ndim - 1) + [(0, n_enc - residual.shape[-1])],
        )
    scale = spec.scale
    if scale is not None:
        x, sx = quantize_activations_int8(x)
        scale = sx * scale
    y = vs_matmul(x, spec.vs, bias=bias, residual=residual,
                  fuse_relu=fuse_relu, impl=impl, scale=scale)
    return y[..., :dout] if dout != n_enc else y


# --------------------------------------------------------------------------
# Schema
# --------------------------------------------------------------------------

def net_schema(net: SparseNet) -> dict:
    """P-schema for `models.layers.init_params` from the layer specs.

    BN convs get inference batch-norm parameters (scale/offset/mean/var,
    identity-initialized) instead of a bias; `sparsify` folds them away.
    """
    s = {}
    for l in net.layers:
        if isinstance(l, Conv):
            cin_g = l.cin // l.groups
            e = {
                "w": P((l.kh, l.kw, cin_g, l.cout), (None, None, None, "ff"),
                       fan_in=l.kh * l.kw * cin_g),
            }
            if l.bn:
                e["scale"] = P((l.cout,), ("ff",), init="ones")
                e["offset"] = P((l.cout,), ("ff",), init="zeros")
                e["mean"] = P((l.cout,), ("ff",), init="zeros")
                e["var"] = P((l.cout,), ("ff",), init="ones")
            else:
                e["b"] = P((l.cout,), ("ff",), init="zeros")
            s[l.name] = e
        elif isinstance(l, FC):
            s[l.name] = {
                "w": P((l.din, l.dout), ("fsdp", "ff"), fan_in=l.din),
                "b": P((l.dout,), ("ff",), init="zeros"),
            }
    return s


# --------------------------------------------------------------------------
# Executor
# --------------------------------------------------------------------------

def _bn_fold(p: dict) -> tuple[np.ndarray, np.ndarray]:
    """Inference BN -> (per-cout scale g, bias b): y*g + b == BN(y)."""
    g = (np.asarray(p["scale"], np.float32)
         / np.sqrt(np.asarray(p["var"], np.float32) + BN_EPS))
    b = (np.asarray(p["offset"], np.float32)
         - np.asarray(p["mean"], np.float32) * g)
    return g, b


def _dense_conv(l: Conv, p: dict, x: jax.Array,
                res: jax.Array | None) -> jax.Array:
    """Dense oracle for one Conv layer (BN applied explicitly if present)."""
    w = p["w"].astype(jnp.float32)
    y = dense_conv2d(x.astype(jnp.float32), w, stride=l.stride,
                     groups=l.groups, dilation=l.dilation)
    if "scale" in p:
        g = p["scale"].astype(jnp.float32) * jax.lax.rsqrt(
            p["var"].astype(jnp.float32) + BN_EPS)
        y = (y - p["mean"].astype(jnp.float32)) * g \
            + p["offset"].astype(jnp.float32)
    elif "b" in p:
        y = y + p["b"].astype(jnp.float32)
    if res is not None:
        y = y + res.astype(jnp.float32)
    if l.relu:
        y = jnp.maximum(y, 0.0)
    return y.astype(x.dtype)


def _pool(l: Pool, x: jax.Array) -> jax.Array:
    if l.kind == "gap":
        return jnp.mean(x, axis=(1, 2), keepdims=True)
    stride = l.stride or l.size
    window = (1, l.size, l.size, 1)
    strides = (1, stride, stride, 1)
    if l.kind == "max":
        return jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, window, strides, l.padding)
    if l.kind == "avg":
        s = jax.lax.reduce_window(
            x, 0.0, jax.lax.add, window, strides, l.padding)
        return s / (l.size * l.size)
    raise ValueError(l.kind)


def net_apply(net: SparseNet, params: dict, x: jax.Array, *,
              sparse: dict | None = None, impl: str = "auto",
              collect: list | None = None,
              collect_fc: list | None = None) -> jax.Array:
    """Walk the graph: x (N, H, W, C) -> logits / features.

    sparse: {layer_name: SparseConv | SparseFC | VectorSparse} — layers
    present run the paper's vector-sparse path (weight-side structural skip
    + input-side skip, bias + residual + ReLU fused into the kernel
    epilogue), the dense narrow-Cin stems one XLA dot (`runs_xla_conv`);
    absent layers run dense.  ``collect`` (a list) records
    (name, layer input NHWC, weight, stride) per conv for the accelerator
    cycle model; ``collect_fc`` (a separate list, so the conv record's
    shape stays stable for its consumers) records (name, layer input,
    weight) per FC layer — the calibration harness measures FC layers on
    their real flattened activations through this hook.
    """
    sparse = sparse or {}
    saved: dict[str, jax.Array] = {}
    for l in net.layers:
        # trace-time only: the layer's ops carry its name in their HLO
        # metadata (``op_name``), which ties device ops to layers
        with jax.named_scope(getattr(l, "name", type(l).__name__)):
            if isinstance(l, Save):
                saved[l.key] = x
            elif isinstance(l, Conv):
                xin = saved[l.src] if l.src else x
                res = saved[l.residual] if l.residual else None
                p = params[l.name]
                if collect is not None:
                    collect.append((l.name, xin, p["w"], l.stride, l.groups,
                                    l.dilation))
                if l.name in sparse:
                    entry = sparse[l.name]
                    spec = (entry if isinstance(entry, SparseConv)
                            else SparseConv(entry))
                    bias = spec.bias if spec.bias is not None else p.get("b")
                    if l.bn and spec.bias is None:
                        # a bare entry can't carry the folded scale/bias —
                        # running it would silently drop batch-norm; demand
                        # `sparsify`'s folded SparseConv instead of
                        # computing wrong activations
                        raise ValueError(
                            f"sparse entry for BN conv {l.name!r} has no "
                            f"folded bias; build it with graph.sparsify "
                            f"(which folds BN into the weights and bias) "
                            f"rather than encoding raw weights")
                    y = apply_sparse_conv(xin, spec, bias=bias,
                                          fuse_relu=l.relu, residual=res,
                                          impl=impl)
                else:
                    y = _dense_conv(l, p, xin, res)
                if l.dst:
                    saved[l.dst] = y
                else:
                    x = y
            elif isinstance(l, ResidualAdd):
                y = x.astype(jnp.float32) + saved[l.key].astype(jnp.float32)
                if l.relu:
                    y = jnp.maximum(y, 0.0)
                x = y.astype(x.dtype)
            elif isinstance(l, Pool):
                x = _pool(l, x)
            elif isinstance(l, Flatten):
                x = x.reshape(x.shape[0], -1)
            elif isinstance(l, FC):
                p = params[l.name]
                if collect_fc is not None:
                    collect_fc.append((l.name, x, p["w"]))
                if l.name in sparse:
                    entry = sparse[l.name]
                    spec = (entry if isinstance(entry, SparseFC)
                            else SparseFC(entry))
                    bias = spec.bias if spec.bias is not None else p["b"]
                    x = apply_sparse_fc(x, spec, bias=bias,
                                        fuse_relu=l.relu, impl=impl)
                else:
                    y = jnp.dot(x, p["w"].astype(x.dtype),
                                preferred_element_type=jnp.float32
                                ).astype(x.dtype)
                    y = y + p["b"].astype(y.dtype)
                    x = jax.nn.relu(y) if l.relu else y
            else:
                raise TypeError(f"unknown layer spec: {l!r}")
    return x


def input_refusal(image: Any, *, max_size: int | None = None,
                  channels: int | None = None) -> str | None:
    """Admission-time validation of one serving input image.

    Returns a machine-readable refusal reason, or None when the image is
    servable.  Serving backends call this *before* a request can join a
    batch, so a malformed input becomes a structured refusal instead of a
    mid-wave shape/dtype error that takes the whole batch down.  The
    checks mirror what `net_apply` actually requires of one (H, W, C)
    image: a rank-3 float array of finite values, within the net's fixed
    input size (``max_size``) when it has one.
    """
    if not isinstance(image, np.ndarray):
        return f"not_an_array:{type(image).__name__}"
    if image.ndim != 3:
        return f"bad_rank:{image.ndim}"
    if not np.issubdtype(image.dtype, np.floating):
        return f"bad_dtype:{image.dtype}"
    if image.size == 0:
        return "empty_image"
    h, w, c = image.shape
    if channels is not None and c != channels:
        return f"bad_channels:{c}"
    if max_size is not None and max(h, w) > max_size:
        return f"oversize:{h}x{w}>{max_size}"
    if not bool(np.isfinite(image).all()):
        return "non_finite_input"
    return None


def output_finite(emission: Any) -> bool:
    """Output-validation guard predicate: True iff every value in one
    emission (a logits row) is finite.  The fleet scheduler uses this to
    quarantine a replica whose wave produced NaN/inf instead of delivering
    the garbage (`launch.scheduler.FleetScheduler`)."""
    arr = np.asarray(emission)
    if not np.issubdtype(arr.dtype, np.floating):
        return True
    return bool(np.isfinite(arr).all())


@dataclasses.dataclass
class BatchedApply:
    """Batched serving entry point: `net_apply` behind a jit-compile cache.

    One compiled executable per (net, weight set, impl, input-shape
    bucket): the serving scheduler pads request batches onto a small set of
    shape buckets, so steady-state traffic never recompiles — the cache hit
    is the hot path.  The key includes the identity of the closed-over
    params/sparse trees (two nets sharing a name never alias each other's
    weights); ``key`` adds a readable variant tag (e.g. ``(density,)``) so
    one *shared* ``cache`` dict can hold several sparsified nets side by
    side.  By default each instance gets its own cache.

    ``xla_convs`` is the number of conv layers each executable runs
    through XLA instead of a kernel (`runs_xla_conv`), and ``dw_convs``
    the number of depthwise conv layers it runs (`is_depthwise_conv`),
    both fixed when the instance is built: the same for every shape
    bucket.

    Sharded compile path: when ``mesh`` (+ ``rules``) is set, tracing and
    execution run inside ``sharding.use_mesh(mesh, rules)`` and the cache
    key includes the mesh, so a weight tree whose leaves carry
    `NamedSharding`s (see `shard_sparse`) compiles to a GSPMD-partitioned
    executable — e.g. an FC head cout-sharded over the ``model`` axis runs
    each device's strip slice locally and all-gathers the logits in the
    epilogue.
    """

    net: SparseNet
    params: dict
    sparse: dict | None = None
    impl: str = "auto"
    key: tuple = ()
    cache: dict = dataclasses.field(default_factory=dict)
    mesh: object = None
    rules: object = None
    xla_convs: int = dataclasses.field(init=False)
    dw_convs: int = dataclasses.field(init=False)

    def __post_init__(self) -> None:
        entries = (self.sparse or {}).values()
        self.xla_convs = sum(runs_xla_conv(e, self.impl) for e in entries)
        self.dw_convs = sum(map(is_depthwise_conv, entries))

    def cache_key(self, shape: tuple) -> tuple:
        # id() is stable and unique here: self (and every cached closure)
        # keeps the weight trees alive
        return (self.net.name, id(self.params), id(self.sparse), self.key,
                self.impl, id(self.mesh), tuple(shape))

    def _jitted(self, shape: tuple) -> Any:
        k = self.cache_key(shape)
        fn = self.cache.get(k)
        if fn is None:
            net, params = self.net, self.params
            sparse, impl = self.sparse, self.impl
            fn = jax.jit(lambda xx: net_apply(net, params, xx,
                                              sparse=sparse, impl=impl))
            self.cache[k] = fn
        return fn

    def _context(self) -> Any:
        if self.mesh is None:
            return contextlib.nullcontext()
        from repro.parallel import sharding as shd
        return shd.use_mesh(self.mesh, self.rules or shd.SERVE_RULES)

    def __call__(self, x: jax.Array) -> jax.Array:
        fn = self._jitted(x.shape)
        with self._context():
            return fn(x)

    def lower(self, x: Any) -> jax.stages.Lowered:
        """Ahead-of-time lowering of the executable serving ``x``'s shape
        bucket (``x`` an array or a `jax.ShapeDtypeStruct`): ``.compile()``
        then ``.as_text()`` shows what the device runs."""
        fn = self._jitted(x.shape)
        with self._context():
            return fn.lower(x)

    @property
    def compiles(self) -> int:
        """Distinct compiled entries in the cache (all variants)."""
        return len(self.cache)


def shard_sparse(sparse: dict, *, ctx: Any = None) -> dict:
    """Device-place a `sparsify` tree under the active mesh context.

    FC heads shard over their output strips: `VectorSparse.vals`
    (NB, S, vk, vn) and ``idx`` (NB, S) split on the leading NB axis — the
    cout strip axis, the paper's per-strip PE-block parallelism — via the
    ``ff`` logical rule (``model`` mesh axis by default); the bias stays
    replicated (it is sliced per-strip inside the epilogue by GSPMD).
    Conv entries follow the ``conv`` rule, replicated by default (serving
    shards the cheap wide FC heads; convs scale across replicas instead) —
    map ``conv`` to a mesh axis to cout-shard them the same way.  Strip
    counts that don't divide the mesh axis demote to replicated
    (`sharding.spec_for`), so odd heads degrade gracefully.  Biases,
    scales and a stem's dense weight are replicated.
    """
    from repro.parallel import sharding as shd

    ctx = ctx or shd.current()
    assert ctx is not None, "shard_sparse requires an active use_mesh()"

    def place(arr: jax.Array, axes: tuple) -> jax.Array:
        s = shd.named_sharding(axes, shape=arr.shape, ctx=ctx)
        return jax.device_put(arr, s)

    def replicate(arr: jax.Array | None) -> jax.Array | None:
        return None if arr is None else place(arr, (None,) * arr.ndim)

    def place_vs(vs: VectorSparse, axis: str) -> VectorSparse:
        return VectorSparse(
            vals=place(vs.vals, (axis, None, None, None)),
            idx=place(vs.idx, (axis, None)),
            shape=vs.shape)

    out = {}
    for name, entry in sparse.items():
        if isinstance(entry, SparseFC):
            out[name] = dataclasses.replace(
                entry, vs=place_vs(entry.vs, "ff"),
                bias=replicate(entry.bias), scale=replicate(entry.scale))
        elif isinstance(entry, SparseConv):
            out[name] = dataclasses.replace(
                entry, vs=place_vs(entry.vs, "conv"),
                bias=replicate(entry.bias), scale=replicate(entry.scale),
                dense_w=replicate(entry.dense_w))
        else:  # bare VectorSparse entry (FC-style)
            out[name] = place_vs(entry, "ff")
    return out


def collect_conv_traffic(net: SparseNet, params: dict,
                         x: jax.Array) -> list:
    """Forward pass recording (name, conv input NHWC, weight, stride,
    groups, dilation) per conv layer — the input of
    `core.accel_model.network_cycle_reports` / `network_traffic_reports`."""
    rec: list = []
    net_apply(net, params, x, collect=rec)
    return rec


# --------------------------------------------------------------------------
# Generic sparsification (BN folding + vector pruning + remainder strips)
# --------------------------------------------------------------------------

def sparsify(net: SparseNet, params: dict, density: float, *,
             vk: int = 32, vn: int = 128,
             include_fc: bool = True, dtype: Any = None) -> tuple[dict, dict]:
    """Vector-prune a whole network to `density` (fraction of kept vectors).

    Returns ``(sparse, pruned)``:

    * ``sparse`` — {layer name: SparseConv | SparseFC} for `net_apply`.
      Every conv is encoded — BN is folded into the weights and a bias
      *before* pruning (so pruning scores see the true inference
      magnitudes), small-Cin stems keep their weights (density 1, standard
      pruning practice, `keeps_dense`) with input channels zero-padded to
      a tileable K, and non-tileable FC heads get a zero-padded remainder
      strip.  A float stem also keeps its folded dense weight
      (``SparseConv.dense_w``): it runs as one XLA dot, every other layer
      on the sparse kernels.
    * ``pruned`` — a dense param tree computing the identical function
      (folded weights + bias; BN entries replaced by a plain bias), the
      oracle for parity tests.

    ``dtype=jnp.int8`` (or ``"int8"``) quantizes every encoded weight
    per-cout symmetric from the pruned folded-BN weights and stores the
    dequant scales on the specs; the pruned dense tree then holds the
    DEQUANTIZED f32 weights, so the oracle and cycle model see exactly the
    values the int8 kernels reconstruct.
    """
    int8 = _wants_int8(dtype)
    sparse: dict = {}
    pruned = {name: dict(entry) for name, entry in params.items()}
    for l in net.layers:
        if isinstance(l, Conv):
            p = params[l.name]
            wdt = p["w"].dtype
            w = np.asarray(p["w"], np.float32)
            cin_g = w.shape[2]  # channels per group (== cin when ungrouped)
            if l.bn:
                g, b = _bn_fold(p)
                w = w * g  # scale per cout (last axis)
            elif "b" in p:
                b = np.asarray(p["b"], np.float32)
            else:
                b = np.zeros((w.shape[3],), np.float32)
            prune = not keeps_dense(l.groups, cin_g, vk)
            spec, wp = sparse_conv_from_dense(
                w, density, vk=vk, vn=vn, stride=l.stride, groups=l.groups,
                dilation=l.dilation, prune=prune,
                dtype=jnp.int8 if int8 else wdt,
                allow_fallback=l.allow_fallback, path=f"{net.name}/{l.name}",
            )
            spec.bias = jnp.asarray(b, wdt)
            sparse[l.name] = spec
            pruned[l.name] = {"w": jnp.asarray(wp, wdt),
                              "b": jnp.asarray(b, wdt)}
            if not prune and not int8:
                spec.dense_w = jnp.asarray(s2d_weight_matrix(
                    wp, stride=l.stride, dilation=l.dilation), wdt)
        elif isinstance(l, FC) and include_fc:
            p = params[l.name]
            wdt = p["w"].dtype
            w = np.asarray(p["w"], np.float32)
            din, dout = w.shape
            fg = fc_tile_geometry(din, dout, vk=vk, vn=vn)
            if fg is None:
                continue  # non-tileable K: stays dense (none of our nets)
            wpad = np.pad(w, ((0, 0), (0, fg.pad))) if fg.pad else w
            wp, mask = prune_vectors_balanced(wpad, density, fg.vk, fg.vn)
            if int8:
                s_w = weight_scales(wp)  # pad columns (all-zero) -> 1.0
                wq = quantize_weights_int8(wp, s_w)
                wp = wq.astype(np.float32) * s_w
                vs = from_mask(jnp.asarray(wq), mask, fg.vk, fg.vn)
                sparse[l.name] = SparseFC(vs, dout=dout, bias=p["b"],
                                          scale=jnp.asarray(s_w))
            else:
                vs = from_mask(jnp.asarray(wp, wdt), mask, fg.vk, fg.vn)
                sparse[l.name] = SparseFC(vs, dout=dout, bias=p["b"])
            pruned[l.name] = {"w": jnp.asarray(wp[:, :dout], wdt),
                              "b": p["b"]}
    return sparse, pruned


# --------------------------------------------------------------------------
# Builders
# --------------------------------------------------------------------------

# channels per conv layer; 'M' = 2x2 max-pool
VGG16_LAYERS = [64, 64, "M", 128, 128, "M", 256, 256, 256, "M",
                512, 512, 512, "M", 512, 512, 512, "M"]


def build_vgg16(num_classes: int = 1000, *, image_size: int = 224) -> SparseNet:
    """The paper's evaluation model: 13 convs + 3 FC, classic VGG (no BN)."""
    layers: list = []
    cin, i = 3, 1
    for c in VGG16_LAYERS:
        if c == "M":
            layers.append(Pool("max", 2))
        else:
            layers.append(Conv(f"conv{i}", cin, c))
            cin, i = c, i + 1
    fc_in = 512 * (image_size // 32) ** 2
    layers += [
        Flatten(),
        FC("fc1", fc_in, 4096),
        FC("fc2", 4096, 4096),
        Classifier("fc3", 4096, num_classes),
    ]
    return SparseNet("vgg16", tuple(layers))


# (channels, blocks) per stage — the ResNet-18 basic-block plan.
RESNET18_STAGES = ((64, 2), (128, 2), (256, 2), (512, 2))


def _basic_block(layers: list, prefix: str, cin: int, cout: int,
                 stride: int) -> None:
    """Append one ResNet basic block: conv-BN-ReLU -> conv-BN -> (+id) ReLU.

    The shortcut is the saved block input, or a stride-matched 1x1
    BN-projection of it when the shape changes; either way it is added in
    conv2's fused epilogue (Conv.residual), before the final ReLU.
    """
    inkey = f"{prefix}_in"
    layers.append(Save(inkey))
    idkey = inkey
    if stride != 1 or cin != cout:
        idkey = f"{prefix}_id"
        layers.append(Conv(f"{prefix}_down", cin, cout, 1, 1, stride,
                           bn=True, relu=False, src=inkey, dst=idkey))
    layers.append(Conv(f"{prefix}_conv1", cin, cout, 3, 3, stride, bn=True))
    layers.append(Conv(f"{prefix}_conv2", cout, cout, 3, 3, 1, bn=True,
                       residual=idkey))


def build_resnet18(num_classes: int = 1000, *,
                   image_size: int = 224) -> SparseNet:
    """ResNet-18: 7x7/s2 BN stem, 3x3/s2 max-pool, 4 stages x 2 basic
    blocks (stride-2 1x1 BN-projection downsamples), GAP, 512-d classifier.

    Every conv geometry here — 7x7/s2, 3x3/s1, 3x3/s2, 1x1/s2 — maps onto
    the generalized vector-sparse kernel family; residual adds ride the
    fused epilogue and BN folds away at sparsify time, so the whole network
    runs end-to-end on the paper's single sparse datapath.
    """
    del image_size  # geometry is size-agnostic; kept for config symmetry
    layers: list = [
        Conv("conv1", 3, 64, 7, 7, 2, bn=True),
        Pool("max", 3, stride=2, padding="SAME"),
    ]
    cin = 64
    for si, (c, blocks) in enumerate(RESNET18_STAGES):
        for bi in range(blocks):
            stride = 2 if (si > 0 and bi == 0) else 1
            _basic_block(layers, f"layer{si + 1}_{bi}", cin, c, stride)
            cin = c
    layers += [Pool("gap"), Flatten(), Classifier("fc", 512, num_classes)]
    return SparseNet("resnet18", tuple(layers))


# (channels, blocks) per stage — the ResNet-34 basic-block plan: the
# ResNet-50 stage depths on ResNet-18's block type.
RESNET34_STAGES = ((64, 3), (128, 4), (256, 6), (512, 3))


def build_resnet34(num_classes: int = 1000, *,
                   image_size: int = 224) -> SparseNet:
    """ResNet-34: ResNet-18's basic-block architecture at the (3, 4, 6, 3)
    stage depths — no new conv geometry at all (7x7/s2 stem, 3x3 bodies,
    1x1/s2 BN-projection downsamples), so the builder is the whole cost of
    the network; schema, sparsification, serving and the cycle/traffic
    models come from the shared walker."""
    del image_size  # geometry is size-agnostic; kept for config symmetry
    layers: list = [
        Conv("conv1", 3, 64, 7, 7, 2, bn=True),
        Pool("max", 3, stride=2, padding="SAME"),
    ]
    cin = 64
    for si, (c, blocks) in enumerate(RESNET34_STAGES):
        for bi in range(blocks):
            stride = 2 if (si > 0 and bi == 0) else 1
            _basic_block(layers, f"layer{si + 1}_{bi}", cin, c, stride)
            cin = c
    layers += [Pool("gap"), Flatten(), Classifier("fc", 512, num_classes)]
    return SparseNet("resnet34", tuple(layers))


# (bottleneck width, blocks) per stage — ResNet-50's plan; output channels
# are 4x the bottleneck width (the expansion).
RESNET50_STAGES = ((64, 3), (128, 4), (256, 6), (512, 3))


def _bottleneck_block(layers: list, prefix: str, cin: int, c: int,
                      stride: int) -> None:
    """Append one ResNet bottleneck: 1x1 reduce -> 3x3 (stride) -> 1x1
    expand (4x), BN throughout, shortcut added in the expand conv's fused
    epilogue before the final ReLU (a 1x1/stride BN-projection when the
    shape changes)."""
    cout = 4 * c
    inkey = f"{prefix}_in"
    layers.append(Save(inkey))
    idkey = inkey
    if stride != 1 or cin != cout:
        idkey = f"{prefix}_id"
        layers.append(Conv(f"{prefix}_down", cin, cout, 1, 1, stride,
                           bn=True, relu=False, src=inkey, dst=idkey))
    layers.append(Conv(f"{prefix}_conv1", cin, c, 1, 1, 1, bn=True))
    layers.append(Conv(f"{prefix}_conv2", c, c, 3, 3, stride, bn=True))
    layers.append(Conv(f"{prefix}_conv3", c, cout, 1, 1, 1, bn=True,
                       residual=idkey))


def build_resnet50(num_classes: int = 1000, *,
                   image_size: int = 224) -> SparseNet:
    """ResNet-50: the 7x7/s2 BN stem and max-pool of ResNet-18, then 4
    stages of (3, 4, 6, 3) bottleneck blocks (1x1 -> 3x3 -> 1x1 with 4x
    expansion, stride-2 1x1 BN-projection downsamples), GAP, 2048-d
    classifier — the credibility bar SCNN (Parashar et al.) and the
    structured-sparse FPGA accelerator (Zhu et al.) both benchmark.

    Every geometry — 7x7/s2, 1x1/s1, 1x1/s2, 3x3/s1, 3x3/s2 — was already
    expressible in the kernel family; this builder just cashes the IR in.
    """
    del image_size  # geometry is size-agnostic; kept for config symmetry
    layers: list = [
        Conv("conv1", 3, 64, 7, 7, 2, bn=True),
        Pool("max", 3, stride=2, padding="SAME"),
    ]
    cin = 64
    for si, (c, blocks) in enumerate(RESNET50_STAGES):
        for bi in range(blocks):
            stride = 2 if (si > 0 and bi == 0) else 1
            _bottleneck_block(layers, f"layer{si + 1}_{bi}", cin, c, stride)
            cin = 4 * c
    layers += [Pool("gap"), Flatten(), Classifier("fc", 2048, num_classes)]
    return SparseNet("resnet50", tuple(layers))


# (pointwise output channels, depthwise stride) per separable block — the
# standard MobileNetV1 plan after the 3x3/s2/32 stem.
MOBILENET_V1_PLAN = ((64, 1), (128, 2), (128, 1), (256, 2), (256, 1),
                     (512, 2), (512, 1), (512, 1), (512, 1), (512, 1),
                     (512, 1), (1024, 2), (1024, 1))


def build_mobilenet_v1(num_classes: int = 1000, *,
                       image_size: int = 224) -> SparseNet:
    """MobileNetV1: 3x3/s2 stem then 13 depthwise-separable blocks
    (3x3 depthwise BN-ReLU -> 1x1 pointwise BN-ReLU), GAP, 1024-d
    classifier.

    The depthwise stages are ``Conv(groups=cin)`` — the degenerate grouped
    conv routed through the per-channel tap kernels — and every pointwise
    conv is the 1x1 sparse matmul, so the whole efficient-CNN vocabulary
    runs on the one vector-sparse datapath.
    """
    del image_size  # geometry is size-agnostic; kept for config symmetry
    layers: list = [Conv("conv0", 3, 32, 3, 3, 2, bn=True)]
    cin = 32
    for i, (c, s) in enumerate(MOBILENET_V1_PLAN, 1):
        layers.append(Conv(f"dw{i}", cin, cin, 3, 3, s, bn=True,
                           groups=cin))
        layers.append(Conv(f"pw{i}", cin, c, 1, 1, 1, bn=True))
        cin = c
    layers += [Pool("gap"), Flatten(), Classifier("fc", 1024, num_classes)]
    return SparseNet("mobilenet_v1", tuple(layers))


def build_resnet_stem() -> SparseNet:
    """The PR-1 ResNet-style stem (7x7/s2 -> 1x1 -> 3x3/s2), kept as the
    minimal geometry-coverage network (no BN, plain biases)."""
    return SparseNet("resnet_stem", (
        Conv("stem7x7", 3, 64, 7, 7, 2),
        Conv("proj1x1", 64, 128, 1, 1, 1),
        Conv("down3x3", 128, 128, 3, 3, 2),
    ))
