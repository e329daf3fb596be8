"""One general traffic generator, driven by a data file per mix.

A mix (``bench/traffic/<name>.json``) is a closed loop: one client sends
``per_call`` requests in one ``serve`` call and sends the next call when
it returns.  It holds:

  wave        the server's batch width (requests per lockstep wave).
  per_call    requests per ``serve`` call.
  image_side  the side of the square images, in pixels.
  pool        distinct standard-normal images, reused in turn.

Every seed gets the same work: the same calls of the same sizes, on images
drawn from the seed.
"""
from __future__ import annotations

import numpy as np


class Traffic:
    def __init__(self, spec: dict, seed: int):
        self.spec = spec
        self.wave = int(spec["wave"])
        self.per_call = int(spec["per_call"])
        if self.per_call < 1:
            raise ValueError("a mix needs per_call >= 1")
        side = int(spec["image_side"])
        self.pool = np.random.default_rng(seed).standard_normal(
            (int(spec["pool"]), side, side, 3), np.float32)

    def image(self, i: int) -> int:
        """Pool index of request ``i``."""
        return i % len(self.pool)

    def pixels(self, i: int) -> np.ndarray:
        return self.pool[self.image(i)]

    def warm_widths(self) -> list[int]:
        """Request counts of one ``serve`` call each that touch every wave
        width this mix runs: the scheduler shrinks a partial wave of n
        requests to the next power of two."""
        full, rest = divmod(self.per_call, self.wave)
        widths = {self.wave} if full else set()
        if rest:
            widths.add(1 << (rest - 1).bit_length())
        return sorted(widths)
