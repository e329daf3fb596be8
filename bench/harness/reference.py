"""The plain reference: the served network's logits from the seed, written
without any of the program's code.

What the configuration states, and what this module computes from it:

* weights: every conv / fc weight is ``fan_in**-0.5 * normal(k)`` with
  ``k = fold_in(PRNGKey(seed), crc32("['<layer>']['w']"))`` in float32, of
  shape (k, k, cin/groups, cout) for a conv and (din, dout) for an fc;
  fan-in is ``k*k*cin/groups`` for a conv and ``din`` for an fc; biases are
  zero; batch norm is the identity at inference (scale 1, offset 0, mean 0,
  var 1, eps 1e-5).  These are the served model's seeded weights, drawn
  again.
* batch norm folds into the conv weight and a bias before pruning.
* vector pruning, per layer, in float64 scores: the weight as a
  (k*k*cin, cout) matrix (rows ordered ky, kx, cin) cut into (vk, vn) tiles,
  of which each strip of vn output columns keeps its ``round(kb*density)``
  tiles of largest L2 norm.  A conv whose cin is below vk keeps every weight
  (the stem).  An fc pads its output to a multiple of vn with zero columns
  before scoring (the remainder strip).  Output strips are the largest
  divisor of cout up to vn.
* depthwise pruning (groups == cin == cout): the weight as a (k*k, C) tap
  matrix (rows ordered ky, kx) cut into (1, vn) tiles: each strip of vn
  channels keeps its ``max(1, round(k*k*density))`` taps of largest L2
  norm over the strip.  Strips are the largest divisor of C up to vn.  A
  grouped conv with 1 < groups < cin is refused.
* ``round`` is Python's, which rounds halves to even: round(4.5) is 4.
* the forward pass: SAME-padded convs (depthwise ones grouped per channel),
  ReLU, residual adds before the ReLU, max pools, a global average pool, fc
  layers, all in float32.

``precision="highest"`` computes every conv and matmul at full float32 (the
oracle).  ``precision="bf16x3"`` splits each operand into a bfloat16 high and
low part and sums the three products hi*hi + hi*lo + lo*hi with float32
accumulation: the TPU's three-pass ``high`` precision, written out so that
it means the same on every backend (on a TPU it lands 7-10x closer to the
oracle than XLA's own ``Precision.HIGH`` convolutions, so it is the stricter
control).  That is the control, one step below the float32 at highest
precision the configurations state.
"""
from __future__ import annotations

import importlib.util
import pathlib
import zlib

import jax
import jax.numpy as jnp
import numpy as np

BN_EPS = 1e-5
REFERENCE_DIR = pathlib.Path(__file__).resolve().parents[1] / "reference"


def network(config: dict) -> list[dict]:
    """The layer list of ``bench/reference/<config['reference']>.py``."""
    path = REFERENCE_DIR / f"{config['reference']}.py"
    if not path.is_file():
        raise ValueError(f"no reference network file {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_reference_{config['reference']}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.layers(config["num_classes"], config["image_size"])


def _normal(seed: int, name: str, shape: tuple, fan_in: int) -> np.ndarray:
    key = jax.random.fold_in(jax.random.PRNGKey(seed),
                             zlib.crc32(f"['{name}']['w']".encode()))
    w = fan_in ** -0.5 * jax.random.normal(key, shape, jnp.float32)
    return np.asarray(w.astype(jnp.float32))


def _largest_divisor(n: int, cap: int) -> int:
    d = min(n, cap)
    while n % d:
        d -= 1
    return d


def _prune(wm: np.ndarray, density: float, vk: int, vn: int) -> np.ndarray:
    """Keep, in each strip of ``vn`` columns, the ``round(kb*density)``
    (vk, vn) tiles of largest L2 norm; zero the rest."""
    k, n = wm.shape
    tiles = wm.reshape(k // vk, vk, n // vn, vn)
    scores = np.sqrt((tiles.astype(np.float64) ** 2).sum(axis=(1, 3)))
    kb = scores.shape[0]
    keep = max(1, int(round(kb * density)))
    order = np.argsort(-scores, axis=0)
    mask = np.zeros(scores.shape, bool)
    mask[order[:keep], np.arange(scores.shape[1])[None, :]] = True
    full = np.repeat(np.repeat(mask, vk, axis=0), vn, axis=1)
    return (wm * full).astype(np.float32)


def _groups(layer: dict) -> int:
    """A conv's groups: 1, or cin (== cout) for a depthwise conv."""
    g = layer["groups"]
    if g != 1 and not g == layer["cin"] == layer["cout"]:
        raise ValueError(f"{layer['name']}: groups {g} with cin "
                         f"{layer['cin']}, cout {layer['cout']}: only "
                         f"ungrouped and depthwise convs are supported")
    return g


def pruned_weights(layers: list[dict], config: dict, seed: int) -> dict:
    """{layer: (weight, bias)} as the served model computes with them:
    seeded, BN folded, vector-pruned (``config['sparse']``), float32 on the
    host.  Conv weights are HWIO, fc weights (din, dout)."""
    density, vk, vn = (config["weight_density"], config["vk"], config["vn"])
    out = {}
    for l in layers:
        if l["op"] == "conv":
            k, cin, cout = l["k"], l["cin"], l["cout"]
            cin_g = cin // _groups(l)
            w = _normal(seed, l["name"], (k, k, cin_g, cout), k * k * cin_g)
            b = np.zeros((cout,), np.float32)
            if l["bn"]:
                # identity BN: scale 1, var 1 -> per-cout factor g, bias 0
                ones = np.ones((cout,), np.float32)
                w = w * (ones / np.sqrt(ones + BN_EPS))
            if config["sparse"] and l["groups"] > 1:
                wm = _prune(w.reshape(k * k, cout), density, 1,
                            _largest_divisor(cout, vn))
                w = wm.reshape(k, k, 1, cout)
            elif config["sparse"] and cin >= vk:
                if cin % vk:
                    raise ValueError(f"{l['name']}: pruned conv with cin "
                                     f"{cin} not a multiple of vk {vk}")
                wm = _prune(w.reshape(k * k * cin, cout), density, vk,
                            _largest_divisor(cout, vn))
                w = wm.reshape(k, k, cin, cout)
            out[l["name"]] = (w, b)
        elif l["op"] == "fc":
            din, dout = l["din"], l["dout"]
            w = _normal(seed, l["name"], (din, dout), din)
            if config["sparse"]:
                vn_l = min(vn, dout)
                pad = -dout % vn_l
                w = _prune(np.pad(w, ((0, 0), (0, pad))), density, vk,
                           vn_l)[:, :dout]
            out[l["name"]] = (w, np.zeros((dout,), np.float32))
    return out


def _split(a):
    """bfloat16 high and low parts of a float32 array.  The rounding is a
    ``reduce_precision``, which XLA keeps; a float32 -> bfloat16 -> float32
    round trip of converts it may drop (excess precision), and the low part
    would then be zero."""
    hi = jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
    return hi.astype(jnp.bfloat16), (a - hi).astype(jnp.bfloat16)


def _product(op, a, b, precision: str):
    """``op(a, b)`` at float32 highest, or as the bf16x3 control."""
    if precision == "highest":
        return op(a, b, jax.lax.Precision.HIGHEST, jnp.float32)
    if precision != "bf16x3":
        raise ValueError(f"unknown reference precision {precision!r}")
    (ah, al), (bh, bl) = _split(a), _split(b)
    dflt = jax.lax.Precision.DEFAULT
    return (op(ah, bh, dflt, jnp.float32)
            + (op(ah, bl, dflt, jnp.float32) + op(al, bh, dflt, jnp.float32)))


def _conv_op(stride, groups):
    def op(x, w, precision, out_dtype):
        return jax.lax.conv_general_dilated(
            x, w, (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=groups, precision=precision,
            preferred_element_type=out_dtype)
    return op


def _dot(x, w, precision, out_dtype):
    return jnp.matmul(x, w, precision=precision,
                      preferred_element_type=out_dtype)


def forward(layers: list[dict], weights: dict, x, precision: str):
    """Logits of ``x`` (N, H, W, 3) float32; traceable."""
    saved = {}
    for l in layers:
        op = l["op"]
        if op == "save":
            saved[l["key"]] = x
        elif op == "conv":
            w, b = weights[l["name"]]
            xin = saved[l["src"]] if l["src"] else x
            y = _product(_conv_op(l["stride"], _groups(l)), xin, w,
                         precision) + b
            if l["residual"]:
                y = y + saved[l["residual"]]
            if l["relu"]:
                y = jnp.maximum(y, 0.0)
            if l["dst"]:
                saved[l["dst"]] = y
            else:
                x = y
        elif op == "pool":
            if l["kind"] == "gap":
                x = jnp.mean(x, axis=(1, 2), keepdims=True)
            elif l["kind"] == "max":
                s, st = l["size"], l["stride"]
                x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                          (1, s, s, 1), (1, st, st, 1),
                                          l["padding"])
            else:
                raise ValueError(f"unknown pool {l['kind']!r}")
        elif op == "flatten":
            x = x.reshape(x.shape[0], -1)
        elif op == "fc":
            w, b = weights[l["name"]]
            x = _product(_dot, x, w, precision) + b
            if l["relu"]:
                x = jnp.maximum(x, 0.0)
        else:
            raise ValueError(f"unknown layer op {op!r}")
    return x


class Reference:
    """The reference network of one configuration and seed, on the default
    device, evaluated in blocks of ``block`` images."""

    def __init__(self, config: dict, seed: int, *, block: int = 16):
        self.layers = network(config)
        host = pruned_weights(self.layers, config, seed)
        self.weights = jax.device_put(host)
        self.block = block
        self._fns = {}

    def logits(self, images: np.ndarray, precision: str = "highest"
               ) -> np.ndarray:
        fn = self._fns.get(precision)
        if fn is None:
            layers = self.layers
            fn = jax.jit(lambda w, x: forward(layers, w, x, precision))
            self._fns[precision] = fn
        out, n, b = [], len(images), self.block
        for i in range(0, n, b):
            blk = images[i:i + b]
            if len(blk) < b:
                blk = np.concatenate(
                    [blk, np.zeros((b - len(blk), *blk.shape[1:]),
                                   blk.dtype)])
            out.append(np.asarray(fn(self.weights, jnp.asarray(blk))))
        return np.concatenate(out)[:n]
