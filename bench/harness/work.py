"""The work a served network needs, counted from its shapes, and the chip's
peaks.

FLOPs are 2 x (kept weights) x (output positions) per layer: the
multiply-adds the vector-sparse model must do, not the dense network's.
Kept weights follow the pruning rule of ``harness.reference``: a pruned
layer keeps ``round(kb * density)`` (vk, vn) tiles in each output strip;
a conv whose cin is below vk keeps all its weights; an fc's remainder strip
counts only its real columns; a depthwise conv (groups == cin == cout)
keeps ``round(k*k * density)`` of its k*k taps in every channel.  A
grouped conv with 1 < groups < cin is refused.  Minimum bytes per wave
are the kept weights once, plus each image's layer input, output and
residual once.  Pools, pads and layout copies are not counted: they are
not work the network needs.
"""
from __future__ import annotations

import dataclasses
import json
import math
import pathlib

PEAKS_FILE = pathlib.Path(__file__).resolve().parents[1] / "peaks.json"
DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "int8": 1}


@dataclasses.dataclass(frozen=True)
class LayerWork:
    name: str
    flops: int           # per image
    weight_bytes: int    # per wave
    act_bytes: int       # per image: input + output + residual


def _kept(rows: int, cols: int, vk: int, vn: int, density: float,
          prune: bool) -> int:
    """Kept weights of a (rows, cols) matrix under balanced tile pruning,
    the cols padded to a multiple of vn (real columns counted only)."""
    if not prune:
        return rows * cols
    if rows % vk:
        raise ValueError(f"pruned matrix rows {rows} not a multiple of {vk}")
    kb = rows // vk
    return max(1, int(round(kb * density))) * vk * cols


def _largest_divisor(n: int, cap: int) -> int:
    d = min(n, cap)
    while n % d:
        d -= 1
    return d


def network_work(layers: list[dict], config: dict) -> list[LayerWork]:
    """Per-layer work of one image through ``layers`` at the config's image
    size, density and dtype."""
    side = config["image_size"]
    h = w = side
    c = 3
    nbytes = DTYPE_BYTES[config["dtype"]]
    vk, vn, density = config["vk"], config["vn"], config["weight_density"]
    sparse = config["sparse"]
    shapes: dict[str, tuple[int, int, int]] = {}
    out = []
    for l in layers:
        op = l["op"]
        if op == "save":
            shapes[l["key"]] = (h, w, c)
        elif op == "conv":
            hi, wi, ci = shapes[l["src"]] if l["src"] else (h, w, c)
            s, k = l["stride"], l["k"]
            ho, wo = math.ceil(hi / s), math.ceil(wi / s)
            cout = l["cout"]
            if cout % _largest_divisor(cout, vn):
                raise ValueError(f"{l['name']}: cout {cout} does not tile")
            if l["groups"] == 1:
                kept = _kept(k * k * ci, cout, vk, vn, density,
                             sparse and ci >= vk)
            elif l["groups"] == ci == cout:
                # the (k*k, C) tap matrix in (1, vn) tiles
                kept = _kept(k * k, cout, 1, vn, density, sparse)
            else:
                raise ValueError(f"{l['name']}: groups {l['groups']} with "
                                 f"{ci} input and {cout} output channels: "
                                 f"only ungrouped and depthwise convs are "
                                 f"counted")
            # a 1x1 strided conv needs only the pixels it samples
            pin = ho * wo if k == 1 else hi * wi
            act = pin * ci + ho * wo * cout * (2 if l["residual"] else 1)
            out.append(LayerWork(l["name"], 2 * kept * ho * wo,
                                 kept * nbytes, act * nbytes))
            if l["dst"]:
                shapes[l["dst"]] = (ho, wo, cout)
            else:
                h, w, c = ho, wo, cout
        elif op == "pool":
            if l["kind"] == "gap":
                h = w = 1
            elif l["padding"] == "SAME":
                h, w = math.ceil(h / l["stride"]), math.ceil(w / l["stride"])
            else:
                h = (h - l["size"]) // l["stride"] + 1
                w = (w - l["size"]) // l["stride"] + 1
        elif op == "flatten":
            h, w, c = 1, 1, h * w * c
        elif op == "fc":
            din, dout = l["din"], l["dout"]
            if din != h * w * c:
                raise ValueError(f"{l['name']}: din {din} != {h * w * c}")
            kept = _kept(din, dout, vk, vn, density, sparse)
            out.append(LayerWork(l["name"], 2 * kept, kept * nbytes,
                                 (din + dout) * nbytes))
            h, w, c = 1, 1, dout
    return out


def load_peaks(device_kind: str, dtype: str) -> tuple[float, float]:
    """(FLOP/s, HBM bytes/s) of ``device_kind`` for ``dtype``; a device
    missing from ``bench/peaks.json`` is an error."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; known: {sorted(table)}")
    row = table[device_kind]
    return float(row["flops_per_s"][dtype]), float(row["hbm_bytes_per_s"])


def roofline_seconds(work: list[LayerWork], waves: list[int],
                     flops_per_s: float, bytes_per_s: float) -> float:
    """The least time the given waves (images in each) take: per layer and
    wave, the larger of its FLOPs over the peak and its bytes over the
    bandwidth, summed."""
    return sum(
        max(lw.flops * n / flops_per_s,
            (lw.weight_bytes + lw.act_bytes * n) / bytes_per_s)
        for n in waves if n for lw in work)
