"""Drive the served path: ``CNNServer.serve`` under a traffic mix.

The harness is the client.  It builds ``ImageRequest``s, hands them to
``serve`` and records, per request, when it was sent and when its ``serve`` call
returned with its logits.  It also logs each of its own steps (``window``,
``serve``, ``build_requests``) as a span on the wall clock, so a device
trace's idle gaps can be attributed to what the host was doing.  (The profiler's own host tracer
would record the same spans, but it records about a million host events per
second of this workload and slows the host it is measuring.)
"""
from __future__ import annotations

import contextlib
import dataclasses
import time

import numpy as np

from harness.traffic import Traffic


@dataclasses.dataclass
class Record:
    """What one window did.  Times are seconds from the window's start."""

    due: np.ndarray                 # per request: when it was sent
    end: np.ndarray                 # when its serve call returned
    delivered: np.ndarray           # bool
    logits: dict                    # request index -> (classes,) float32
    calls: list                     # (start, end, requests) per serve call
    elapsed: float                  # window start -> last call's return

    @property
    def attempted(self) -> int:
        return len(self.due)

    @property
    def latency(self) -> np.ndarray:
        return self.end - self.due


def make_server(config: dict, traffic: Traffic, seed: int):
    """The served model of ``config`` with the mix's wave width, weights
    from ``seed`` (the program draws them itself)."""
    from repro.configs import get_config
    from repro.launch.serve import CNNServer

    cfg = dataclasses.replace(get_config(config["arch"]),
                              image_size=config["image_size"],
                              num_classes=config["num_classes"],
                              weight_density=config["weight_density"],
                              vk=config["vk"], vn=config["vn"])
    return CNNServer(cfg, batch=traffic.wave, seed=seed,
                     sparse=config["sparse"])


class Driver:
    def __init__(self, server, traffic: Traffic):
        from repro.launch.serve import ImageRequest

        self.server = server
        self.traffic = traffic
        self._request = ImageRequest
        self.spans: list = []  # (name, start, end) in wall-clock ns

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.time_ns()
        try:
            yield
        finally:
            self.spans.append((name, t0, time.time_ns()))

    def _requests(self, ids) -> list:
        with self.span("build_requests"):
            return [self._request(rid=int(i), image=self.traffic.pixels(i))
                    for i in ids]

    def _serve(self, reqs: list) -> None:
        with self.span("serve"):
            self.server.serve(reqs)

    def warm(self) -> None:
        """One call per wave width this mix runs."""
        for width in self.traffic.warm_widths():
            self._serve(self._requests(range(width)))

    def run(self, seconds: float) -> Record:
        """Closed loop: the next call is sent when the last one returns,
        until ``seconds`` have passed."""
        self.spans = []
        with self.span("window"):
            rec = {k: [] for k in ("due", "end", "ok", "calls")}
            logits = {}
            n = self.traffic.per_call
            clock = time.perf_counter
            w0 = clock()
            i = 0
            while clock() - w0 < seconds:
                rec["due"] += [clock() - w0] * n
                reqs = self._requests(range(i, i + n))
                t0 = clock() - w0
                self._serve(reqs)
                t1 = clock() - w0
                rec["calls"].append((t0, t1, n))
                outcomes = self.server.outcomes
                for r in reqs:
                    ok = (getattr(outcomes.get(r.rid), "status", None)
                          == "delivered" and r.logits is not None)
                    rec["end"].append(t1)
                    rec["ok"].append(ok)
                    if ok:
                        logits[r.rid] = np.asarray(r.logits, np.float32)
                i += n
            return Record(due=np.array(rec["due"]), end=np.array(rec["end"]),
                          delivered=np.array(rec["ok"], bool), logits=logits,
                          calls=rec["calls"], elapsed=clock() - w0)
