"""Layer records of the reference networks (``bench/reference/<arch>.py``).

A network is a flat list of dicts, each with ``op`` and its fields:

  conv     name, cin, cout, k, stride, bn, relu, residual, src, dst, groups
           (k x k, SAME padding; ``residual`` names a saved tensor added
           before the ReLU; ``src``/``dst`` read the input from / write the
           output to a saved slot instead of the stream; ``groups`` is 1,
           or ``cin == cout`` for a depthwise conv, one k x k filter per
           channel; other groupings are refused)
  pool     kind ('max' | 'gap'), size, stride, padding ('SAME' | 'VALID')
  save     key
  flatten
  fc       name, din, dout, relu

Parameter names are those the served model's seeded init keys its weights
by, so the reference draws the same weights from the same seed.
"""
from __future__ import annotations


def conv(name: str, cin: int, cout: int, k: int, stride: int = 1, *,
         bn: bool = False, relu: bool = True, residual: str | None = None,
         src: str | None = None, dst: str | None = None,
         groups: int = 1) -> dict:
    return dict(op="conv", name=name, cin=cin, cout=cout, k=k, stride=stride,
                bn=bn, relu=relu, residual=residual, src=src, dst=dst,
                groups=groups)


def fc(name: str, din: int, dout: int, relu: bool = True) -> dict:
    return dict(op="fc", name=name, din=din, dout=dout, relu=relu)


def pool(kind: str, size: int = 2, stride: int | None = None,
         padding: str = "VALID") -> dict:
    return dict(op="pool", kind=kind, size=size, stride=stride or size,
                padding=padding)


def save(key: str) -> dict:
    return dict(op="save", key=key)


def flatten() -> dict:
    return dict(op="flatten")
