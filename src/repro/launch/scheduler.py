"""Model-agnostic lockstep scheduler: queue, batch bucketing, slot
retirement, backfill — plus fleet-level fault tolerance.

The scheduler owns *when* things run — admission from the queue, bucketing
requests that may share a batch, the slot lifecycle (live -> retired ->
backfilled) — and a backend owns *what* runs (the model math).  The LM
prefill/decode stack and the CNN `SparseNet.apply` path both plug in here
(`launch.serve.LMBackend` / `launch.serve.CNNBackend`), so retirement and
backfill are one tested code path instead of per-model loop bodies.

Backend protocol (duck-typed)
-----------------------------
  bucket_key(req) -> hashable
      Requests sharing a key may share a lockstep batch (LM: prompt-length
      bucket; CNN: padded image shape).
  sort_key(req) -> sortable
      Admission order within a bucket (LM: longest prompt first, so every
      later backfill fits the already-grown context).
  context() -> context manager
      Entered around one whole lockstep run (mesh/sharding scope).
  start(reqs, width) -> (state, emissions | None)
      Admit the first wave into a width-slot batch (LM: prefill, emitting
      each slot's first token; CNN: nothing to emit before the first step).
  step(state, slots) -> (state, emissions)
      One lockstep step over all slots; ``slots`` is the width-long list of
      in-flight requests (None = idle lane).  Emissions is per-slot.
  append(req, emission) -> bool
      Record one emission on the request; True means the request finished
      (EOS, token budget, or — for one-shot image requests — always).
  can_backfill(state, req) -> bool
      May ``req`` join this in-flight run?  (LM: its prompt fits the
      current context length and capacity; CNN: same shape bucket.)
  backfill(state, slot, req) -> (state, emission | None)
      Admit ``req`` into freed slot ``slot`` mid-run (LM: prefill padded to
      the current context and merge its cache rows into the live batch).
  finish(state) -> dict
      Backend-specific stats merged into the run's stats dict.

Optional protocol extensions (run-ahead, fault tolerance, admission
control):

  dispatch(state, slots) -> handle
  collect(state, handle, slots) -> (state, emissions)
      ``step`` split in two: ``dispatch`` issues the wave and returns
      without blocking on its result, ``collect`` blocks and emits.
  one_shot = True
      Every request finishes on the first emission of the wave that
      computes it (``append`` returns True), ``backfill`` emits nothing,
      and neither ``can_backfill`` nor ``backfill`` reads the emissions of
      a wave still in flight.  A one-shot backend with the split runs one
      wave ahead in `LockstepScheduler.run_lockstep`: the successor of
      wave k (the requests `_deliver`'s first-fit backfill would pop for
      the slots wave k frees, in the same order) is dispatched before wave
      k is collected, so its host work overlaps wave k's device work.  At
      most two waves are in flight.  Any other backend keeps the
      synchronous loop: ``step``, deliver, backfill, ``step``.

  validate_request(req) -> str | None
      Admission-time request validation: a refusal reason string rejects
      the request with a structured `RequestOutcome` *before* it can cause
      a mid-wave shape/dtype error; None admits it.
  check_emission(emission) -> bool
      Output-validation guard: False means the emission is corrupt (e.g.
      non-finite logits).  The fleet scheduler quarantines the producing
      replica and re-serves the wave instead of delivering garbage.
  reset(req) -> None
      Clear a request's partial progress before it is re-served after a
      replica fault.  Backends without ``reset`` get partially-delivered
      requests refused (``partial_stream_lost``) rather than duplicated.

A finished request frees its slot *immediately*: the scheduler scans the
bucket queue first-fit and backfills in the same delivery pass, chaining if
the newcomer itself finishes instantly (e.g. ``max_new=1``: its admission
emission already completes it).  A run ends when every slot is idle; a
bucket's leftover requests that never fit an in-flight run (capacity,
context length) get a fresh lockstep run of their own.

Replica fleet
-------------
`FleetScheduler` scales the same protocol across N data-parallel backend
replicas (one weight copy per replica, typically device-placed — see
`launch.serve.ReplicaGroup`).  Admission becomes *per-replica bucket
ladders*: each bucket's sorted queue is cut into wave-sized chunks placed
on the least-loaded replica.  The run loop interleaves the replicas'
lockstep runs one step per tick — per-replica wave dispatch, so a slow
wave on replica 0 never stalls retirement or backfill on replicas
1..N-1 — and an idle replica *steals* the tail half of the longest queue
still waiting on any other replica.  With the ``dispatch``/``collect``
split one tick issues every replica's computation before blocking on any
result (JAX async dispatch overlaps the replicas' device work); backends
without the split fall back to the synchronous ``step``.  With one
replica the ladder, admission order and step sequence are exactly
`LockstepScheduler.serve`'s.

Fault tolerance
---------------
Every backend call in the fleet loop is guarded by the typed
`launch.faults.FAULT_TYPES` hierarchy (never a blanket ``except`` —
vscheck VSC304).  Replica health walks ``healthy -> suspect ->
quarantined -> drained``:

  * a transient fault marks the replica *suspect* and re-queues its wave;
    ``suspect_limit`` transients quarantine it;
  * a non-transient fault (`ReplicaDead`, `CompileFault`, the
    `NonFiniteOutput` raised by the output guard) quarantines immediately;
  * quarantine re-places the replica's in-flight slots and pending ladder
    on the surviving replicas (no request lost, no duplicate delivery —
    nothing that reached ``append`` is ever re-served), then marks the
    replica *drained* (terminal).

Per-request budgets are accounted in deterministic wave counts, never the
clock: ``deadline_waves`` refuses a request still *queued* after that many
fleet ticks, ``max_attempts`` bounds fault-driven re-placements.  Bounded
admission (``max_queue``) sheds load at serve() entry.  Every admitted
request ends in exactly one terminal `RequestOutcome` — delivered, or a
structured refusal (reason strings: ``queue_full``, ``invalid:*``,
``deadline_exceeded``, ``retry_budget_exhausted``,
``no_healthy_replicas``, ``partial_stream_lost``) — and control flow stays
clock-free, so a faulty run (chaos-injected or real) is exactly
replayable.

Spans
-----
While a JAX profiler session is on, each ``serve`` call records
`launch.spans`: ``scheduler.serve`` (``requests``), ``scheduler.admit``
(admission, bucketing and sorting; ``refused``), ``scheduler.run`` (one
lockstep run; ``replica``), ``scheduler.deliver`` (one delivery pass) and
``backend.wave`` (one backend step, dispatch to collect; ``wave``,
``replica``, and in a lockstep run ``ahead``: 1 when the wave was
dispatched while the wave before it was still in flight, else 0).  The
clock readings of ``scheduler.run`` are the run stats'
``start_s``/``run_s``; off a profiler session they are the only clock
reads.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time

from repro.launch import spans
from repro.launch.faults import FAULT_TYPES, NonFiniteOutput

__all__ = ["LockstepScheduler", "FleetScheduler", "RequestOutcome",
           "HEALTHY", "SUSPECT", "QUARANTINED", "DRAINED"]


# replica health states (fleet): healthy -> suspect -> quarantined -> drained
HEALTHY = "healthy"
SUSPECT = "suspect"
QUARANTINED = "quarantined"
DRAINED = "drained"


@dataclasses.dataclass
class RequestOutcome:
    """The single terminal outcome of one admitted request.

    ``status`` is ``"delivered"`` or ``"refused"``; refusals carry a
    machine-readable ``reason``.  ``wave`` is the fleet tick (or lockstep
    step) the outcome was decided at, the ``wave`` of the ``backend.wave``
    span that computed a delivered request; ``attempts`` counts
    fault-driven re-placements the request survived before its outcome.
    """

    rid: object
    status: str
    reason: str | None = None
    replica: int | None = None
    attempts: int = 0
    wave: int = 0


def _deliver(be, state, slots, queue, emis, on_finish=None):
    """One delivery pass: append emissions, retire finished requests,
    first-fit backfill from ``queue`` (consumed in place), chaining when a
    backfilled request finishes on its admission emission.  Returns
    ``(state, finished, backfills, emitted)``; ``slots`` mutates in place.
    ``on_finish`` (optional) is called once per retired request.
    """
    finished = backfills = emitted = 0
    with spans.span("scheduler.deliver"):
        for j in range(len(slots)):
            req = slots[j]
            e = None if emis is None else emis[j]
            while req is not None and e is not None:
                done = be.append(req, e)
                emitted += 1
                e = None
                if not done:
                    break
                finished += 1
                if on_finish is not None:
                    on_finish(req)
                state, req, e = _backfill(be, state, queue, j)
                backfills += req is not None
            slots[j] = req
    return state, finished, backfills, emitted


def _backfill(be, state, queue, slot):
    """First fit: pop the first request of ``queue`` that may join the run
    and admit it into ``slot``.  Returns ``(state, req, emission)``, with
    ``req`` None when nothing in the queue fits."""
    for qi, cand in enumerate(queue):
        if be.can_backfill(state, cand):
            req = queue.pop(qi)
            state, e = be.backfill(state, slot, req)
            return state, req, e
    return state, None, None


def _admit(be, requests, outcomes, *, max_queue=None, wave=0):
    """Admission control shared by both schedulers: validate each request
    through the backend's optional ``validate_request`` and shed load
    beyond ``max_queue``.  Refused requests get a structured
    `RequestOutcome`; the admitted remainder is returned in order."""
    validate = getattr(be, "validate_request", None)
    admitted = []
    for req in requests:
        reason = None
        if validate is not None:
            reason = validate(req)
            if reason is not None:
                reason = f"invalid:{reason}"
        if reason is None and max_queue is not None \
                and len(admitted) >= max_queue:
            reason = "queue_full"
        if reason is None:
            admitted.append(req)
        else:
            _record(outcomes, req, RequestOutcome(
                rid=getattr(req, "rid", None), status="refused",
                reason=reason, wave=wave))
    return admitted


def _record(outcomes, req, outcome):
    """Record a terminal outcome exactly once (first one wins)."""
    rid = outcome.rid
    if rid in outcomes:
        return
    outcomes[rid] = outcome
    req.outcome = outcome


class LockstepScheduler:
    """Generic lockstep serving loop over a pluggable model backend.

    ``max_queue`` bounds admission per `serve` call: requests beyond the
    depth are shed with a structured ``queue_full`` refusal (recorded in
    ``self.outcomes``) instead of growing the queue without bound.
    """

    def __init__(self, backend, *, batch: int, max_queue: int | None = None):
        assert batch >= 1
        self.backend = backend
        self.batch = batch
        self.max_queue = max_queue
        self.waves = 0                 # lockstep steps since construction
        self.outcomes: dict = {}

    def serve(self, requests: list) -> list[dict]:
        """Admission-check and bucket the queue, then run lockstep batches
        until it drains.

        Returns one stats dict per lockstep run (see `run_lockstep`);
        per-request terminal outcomes land in ``self.outcomes`` (and on
        each request's ``.outcome``).
        """
        requests = list(requests)
        with spans.top("scheduler.serve", requests=len(requests)):
            self.outcomes = {}
            with spans.span("scheduler.admit") as admit:
                admitted = _admit(self.backend, requests, self.outcomes,
                                  max_queue=self.max_queue)
                buckets: dict = {}
                for r in admitted:
                    buckets.setdefault(self.backend.bucket_key(r),
                                       []).append(r)
                for queue in buckets.values():
                    queue.sort(key=self.backend.sort_key)
                admit.set(refused=len(requests) - len(admitted))
            stats = []
            for queue in buckets.values():
                while queue:
                    stats.append(self.run_lockstep(queue))
            return stats

    def _on_finish(self, wave: int):
        def cb(req):
            _record(self.outcomes, req, RequestOutcome(
                rid=getattr(req, "rid", None), status="delivered",
                wave=wave))
        return cb

    def run_lockstep(self, queue: list) -> dict:
        """One lockstep run: admit up to ``batch`` requests, step until every
        slot retires, backfilling freed slots from ``queue`` (consumed in
        place).  A one-shot backend with the dispatch/collect split runs one
        wave ahead (`_run_ahead`).  Stats: steps, finished, backfills,
        emissions, start_s, run_s (from the clock readings of the run's
        ``scheduler.run`` span), plus whatever `backend.finish` adds.
        """
        be = self.backend
        assert queue, "run_lockstep needs at least one request"
        width = self.batch
        admitted = [queue.pop(0) for _ in range(min(width, len(queue)))]
        slots: list = admitted + [None] * (width - len(admitted))
        n = {"steps": 0, "finished": 0, "backfills": 0, "emissions": 0}
        ctx = getattr(be, "context", None)
        with (ctx() if ctx else contextlib.nullcontext()):
            t0 = time.time_ns()
            run = spans.begin("scheduler.run", t0, replica=0)
            with run:
                state, emis = be.start(admitted, width)
                t1 = time.time_ns()
                state = self._deliver(state, slots, queue, emis, n,
                                      self.waves)
                if getattr(be, "one_shot", False) and hasattr(be, "dispatch"):
                    state = self._run_ahead(state, slots, queue, n)
                while any(s is not None for s in slots):
                    self.waves += 1
                    with spans.span("backend.wave", wave=self.waves,
                                    replica=0, ahead=0):
                        state, emis = be.step(state, slots)
                    n["steps"] += 1
                    state = self._deliver(state, slots, queue, emis, n,
                                          self.waves)
            t2 = time.time_ns()
            run.end(t2)
        out = dict(n, start_s=(t1 - t0) / 1e9, run_s=(t2 - t1) / 1e9)
        out.update(be.finish(state) or {})
        return out

    def _deliver(self, state, slots, queue, emis, n: dict, wave: int):
        state, f, b, e = _deliver(self.backend, state, slots, queue, emis,
                                  self._on_finish(wave))
        n["finished"] += f
        n["backfills"] += b
        n["emissions"] += e
        return state

    def _run_ahead(self, state, slots, queue, n: dict):
        """The waves of a one-shot backend with the dispatch/collect split,
        each dispatched before the wave before it is collected.  A wave's
        successor takes the requests `_deliver` would backfill into the
        slots the wave frees, so every batch, count and outcome is the
        synchronous loop's.  Leaves ``slots`` idle."""
        be = self.backend
        flight = None          # (wave, span, slots, handle) not collected
        while any(s is not None for s in slots):
            self.waves += 1
            span = spans.begin("backend.wave", wave=self.waves, replica=0,
                               ahead=int(flight is not None))
            with span:
                handle = be.dispatch(state, slots)
            n["steps"] += 1
            nxt = [None] * len(slots)
            for j, req in enumerate(slots):
                if req is not None:
                    state, nxt[j], _ = _backfill(be, state, queue, j)
            n["backfills"] += sum(r is not None for r in nxt)
            if flight is not None:
                state = self._collect(state, flight, n)
            flight = (self.waves, span, slots, handle)
            slots = nxt
        if flight is not None:
            state = self._collect(state, flight, n)
        return state

    def _collect(self, state, flight, n: dict):
        wave, span, slots, handle = flight
        with span:
            state, emis = self.backend.collect(state, handle, slots)
        span.end()
        return self._deliver(state, slots, [], emis, n, wave)


class _ReplicaRun:
    """One resumable in-flight lockstep run on one fleet replica.

    The same lifecycle as `LockstepScheduler.run_lockstep`, unrolled so the
    fleet loop can advance many replicas' runs one step at a time: start +
    deliver on construction (the caller pops the admission wave so a
    failing ``start`` can re-queue it), then repeated ``dispatch`` /
    ``collect_and_deliver`` ticks until every slot is idle.  ``guard``
    (optional) validates each wave's emissions before delivery — it raises
    to reject the whole wave (output corruption), so corrupt emissions are
    never appended.  Its ``scheduler.run`` span lasts from construction to
    `finish`, and is the parent of the run's waves and deliveries.
    """

    def __init__(self, replica: int, be, admitted: list, queue: list,
                 width: int, *, on_finish=None, guard=None):
        self.replica = replica
        self.be = be
        self.queue = queue
        self.on_finish = on_finish
        self.guard = guard
        self.slots: list = admitted + [None] * (width - len(admitted))
        self.steps = self.finished = self.backfills = self.emitted = 0
        self._handle = None
        self._wave = spans.OFF
        with self._ctx():
            t0 = time.time_ns()
            self._span = spans.begin("scheduler.run", t0, replica=replica)
            with self._span:
                self.state, emis = be.start(admitted, width)
                self._t1 = time.time_ns()
                self.start_s = (self._t1 - t0) / 1e9
                self._deliver(emis)

    def _ctx(self):
        ctx = getattr(self.be, "context", None)
        return ctx() if ctx else contextlib.nullcontext()

    def _deliver(self, emis):
        if self.guard is not None and emis is not None:
            self.guard(emis)
        self.state, f, b, e = _deliver(
            self.be, self.state, self.slots, self.queue, emis,
            self.on_finish)
        self.finished += f
        self.backfills += b
        self.emitted += e

    def drained(self) -> bool:
        return all(s is None for s in self.slots)

    def in_flight(self) -> list:
        """Requests currently occupying slots (for fault re-placement)."""
        return [s for s in self.slots if s is not None]

    def dispatch(self, wave: int):
        """Issue this replica's step of fleet tick ``wave``; backends with a
        dispatch/collect split return without blocking on the result."""
        fn = getattr(self.be, "dispatch", None)
        with self._ctx(), self._span:
            self._wave = spans.begin("backend.wave", wave=wave,
                                     replica=self.replica)
            with self._wave:
                if fn is not None:
                    self._handle = ("pending", fn(self.state, self.slots))
                else:
                    self._handle = ("ready",
                                    self.be.step(self.state, self.slots))
        self.steps += 1

    def collect_and_deliver(self):
        kind, h = self._handle
        self._handle = None
        with self._ctx(), self._span:
            with self._wave:
                if kind == "pending":
                    self.state, emis = self.be.collect(self.state, h,
                                                       self.slots)
                else:
                    self.state, emis = h
            self._wave.end()
            self._deliver(emis)

    def finish(self) -> dict:
        t2 = time.time_ns()
        self._span.end(t2)
        out = {
            "replica": self.replica,
            "steps": self.steps,
            "finished": self.finished,
            "backfills": self.backfills,
            "emissions": self.emitted,
            "start_s": self.start_s,
            "run_s": (t2 - self._t1) / 1e9,
        }
        with self._ctx():
            out.update(self.be.finish(self.state) or {})
        return out


class FleetScheduler:
    """Data-parallel replica fleet: N backends, per-replica wave dispatch,
    replica health tracking and fault-driven re-placement.

    ``backends`` hold the same model behind the `LockstepScheduler` backend
    protocol, one weight copy each (see module docstring).  ``serve``
    returns one stats dict per lockstep run, tagged with the ``replica``
    that ran it; ``steals`` counts queues moved between replicas since
    construction.  Fault handling (see the module docstring's
    *Fault tolerance* section) is configured by:

      fault_types     exception types treated as replica faults (default
                      `launch.faults.FAULT_TYPES`); anything else
                      propagates — a bug should still fail fast;
      suspect_limit   transient faults a replica survives before
                      quarantine;
      max_attempts    fault-driven re-placements one request survives
                      before a ``retry_budget_exhausted`` refusal;
      deadline_waves  default per-request deadline in fleet ticks (a
                      request may override via its own ``deadline_waves``
                      attribute; None = no deadline);
      max_queue       bounded admission depth (load shedding).

    Health, fault events and per-request outcomes are exposed as
    ``self.health`` / ``self.fault_events`` / ``self.outcomes``.
    """

    def __init__(self, backends: list, *, batch: int,
                 max_queue: int | None = None,
                 deadline_waves: int | None = None,
                 max_attempts: int = 3, suspect_limit: int = 2,
                 fault_types: tuple = FAULT_TYPES):
        assert backends, "FleetScheduler needs at least one backend"
        assert batch >= 1
        self.backends = list(backends)
        self.batch = batch
        self.max_queue = max_queue
        self.deadline_waves = deadline_waves
        self.max_attempts = max_attempts
        self.suspect_limit = suspect_limit
        self.fault_types = fault_types
        self.steals = 0
        self.waves = 0                       # fleet ticks since construction
        self.health = [HEALTHY] * len(self.backends)
        self.fault_counts = [0] * len(self.backends)
        self.fault_events: list[dict] = []
        self.outcomes: dict = {}
        self._attempts: dict = {}

    @property
    def replicas(self) -> int:
        return len(self.backends)

    def _live(self, i: int) -> bool:
        return self.health[i] in (HEALTHY, SUSPECT)

    def live_replicas(self) -> list[int]:
        return [i for i in range(self.replicas) if self._live(i)]

    # -- placement ----------------------------------------------------------

    def _place(self, requests: list) -> list[dict]:
        """Per-replica bucket ladders: each bucket's sorted queue is cut
        into wave-sized chunks placed on the least-loaded replica (by
        queued request count; ties to the lowest index, so one replica
        degenerates to `LockstepScheduler.serve`'s admission order)."""
        ladders: list[dict] = [{} for _ in self.backends]
        self._place_into(requests, ladders)
        return ladders

    def _place_into(self, requests: list, ladders: list[dict]) -> None:
        """Place (or re-place) ``requests`` onto the live replicas'
        ladders, least-loaded first."""
        be0 = self.backends[0]
        live = self.live_replicas()
        assert live, "_place_into requires at least one live replica"
        buckets: dict = {}
        for r in requests:
            buckets.setdefault(be0.bucket_key(r), []).append(r)
        loads = [sum(len(q) for q in lad.values()) for lad in ladders]
        for key, q in buckets.items():
            q.sort(key=be0.sort_key)
            while q:
                chunk = q[: self.batch]
                del q[: self.batch]
                i = min(live, key=lambda j: (loads[j], j))
                ladders[i].setdefault(key, []).extend(chunk)
                loads[i] += len(chunk)

    def _claim(self, i: int, ladders: list[dict], runs: list):
        """Next queue for replica ``i``: its own ladder first, then steal
        the tail half (ceil, so lone stragglers move too) of the longest
        queue still waiting on any other replica — a pending ladder queue,
        or the *queued* remainder of an in-flight run's backfill source
        (admitted slots never move; only requests still waiting do)."""
        ladder = ladders[i]
        for key in list(ladder):
            if ladder[key]:
                return ladder.pop(key)
            del ladder[key]
        victim = None
        for j, other in enumerate(ladders):
            if j != i:
                for q in other.values():
                    if q and (victim is None or len(q) > len(victim)):
                        victim = q
        for run in runs:
            if run is not None and run.replica != i:
                q = run.queue
                if q and (victim is None or len(q) > len(victim)):
                    victim = q
        if victim is None:
            return None
        n = -(-len(victim) // 2)
        stolen = victim[len(victim) - n:]
        del victim[len(victim) - n:]
        self.steals += 1
        return stolen

    def _retire(self, run, ladders: list[dict], stats: list[dict]):
        """Record a drained run; leftover queued requests its backend
        refused to backfill go back on the replica's ladder for a fresh run
        (the `LockstepScheduler.serve` ``while queue`` loop, fleet-wise)."""
        stats.append(run.finish())
        if run.queue:
            key = self.backends[0].bucket_key(run.queue[0])
            ladders[run.replica].setdefault(key, []).extend(run.queue)
            run.queue.clear()

    # -- outcomes -----------------------------------------------------------

    def _refuse(self, req, reason: str) -> None:
        _record(self.outcomes, req, RequestOutcome(
            rid=getattr(req, "rid", None), status="refused", reason=reason,
            attempts=self._attempts.get(id(req), 0), wave=self.waves))

    def _on_finish(self, replica: int):
        def cb(req):
            _record(self.outcomes, req, RequestOutcome(
                rid=getattr(req, "rid", None), status="delivered",
                replica=replica,
                attempts=self._attempts.get(id(req), 0), wave=self.waves))
        return cb

    def _guard(self, be, replica: int):
        """Output-validation guard for one replica's waves: reject a wave
        whose emissions fail the backend's ``check_emission`` by raising
        `NonFiniteOutput` — the tick loop quarantines the replica and
        re-serves the wave elsewhere, so corrupt values never reach
        ``append``."""
        check = getattr(be, "check_emission", None)
        if check is None:
            return None

        def guard(emis):
            bad = [j for j, e in enumerate(emis)
                   if e is not None and not check(e)]
            if bad:
                raise NonFiniteOutput(
                    f"replica {replica} emitted non-finite output in "
                    f"slot(s) {bad}")
        return guard

    # -- fault handling -----------------------------------------------------

    def _log_fault(self, i: int, exc: BaseException) -> None:
        self.fault_events.append({
            "wave": self.waves,
            "replica": i,
            "fault": type(exc).__name__,
            "transient": bool(getattr(exc, "transient", False)),
            "health": self.health[i],
            "error": str(exc),
        })

    def _degrade(self, i: int, exc: BaseException) -> None:
        """Walk replica ``i``'s health state for one fault."""
        if getattr(exc, "transient", False):
            self.fault_counts[i] += 1
            if self.health[i] == HEALTHY:
                self.health[i] = SUSPECT
            if self.fault_counts[i] >= self.suspect_limit:
                self.health[i] = QUARANTINED
        else:
            self.health[i] = QUARANTINED

    def _requeue(self, reqs: list, ladders: list[dict]) -> None:
        """Re-place fault-displaced requests on the surviving replicas.

        Each re-placement spends one retry-budget attempt; a request whose
        delivery already started (partial emissions) is only re-served if
        the backend can ``reset`` it — duplicate delivery is never an
        option.  With no live replica left, everything is refused."""
        be = self.backends[0]
        reset = getattr(be, "reset", None)
        survivors = []
        for req in reqs:
            n = self._attempts.get(id(req), 0) + 1
            self._attempts[id(req)] = n
            if n > self.max_attempts:
                self._refuse(req, "retry_budget_exhausted")
                continue
            if getattr(req, "out", None):
                if reset is None:
                    self._refuse(req, "partial_stream_lost")
                    continue
                reset(req)
            survivors.append(req)
        if not survivors:
            return
        if not self.live_replicas():
            for req in survivors:
                self._refuse(req, "no_healthy_replicas")
            return
        self._place_into(survivors, ladders)

    def _on_fault(self, i: int, exc: BaseException, displaced: list,
                  ladders: list[dict]) -> None:
        """One replica fault: log it, walk the health state, re-place the
        displaced requests, and — on quarantine — drain the replica's
        pending ladder onto the survivors."""
        self._log_fault(i, exc)
        self._degrade(i, exc)
        if self.health[i] == QUARANTINED:
            pending = []
            for q in ladders[i].values():
                pending.extend(q)
            ladders[i].clear()
            displaced = displaced + pending
            self._requeue(displaced, ladders)
            self.health[i] = DRAINED
        else:
            self._requeue(displaced, ladders)

    def _expire(self, ladders: list[dict], runs: list) -> None:
        """Deadline sweep: refuse requests still *queued* (not in-flight)
        after their wave budget.  ``deadline_waves`` counts fleet ticks
        since this serve() started; in-flight requests always complete."""
        default = self.deadline_waves
        age = self.waves - self._tick0
        queues = [q for lad in ladders for q in lad.values()]
        queues += [run.queue for run in runs if run is not None]
        for q in queues:
            keep = []
            for req in q:
                dl = getattr(req, "deadline_waves", None)
                dl = default if dl is None else dl
                if dl is not None and age >= dl:
                    self._refuse(req, "deadline_exceeded")
                else:
                    keep.append(req)
            q[:] = keep

    def _spawn(self, i: int, q: list, ladders: list[dict]):
        """Admit a wave from queue ``q`` on replica ``i``.  Returns the
        live `_ReplicaRun`, or None if ``start`` faulted (the wave is
        re-queued and the replica's health degraded)."""
        be = self.backends[i]
        admitted = [q.pop(0) for _ in range(min(self.batch, len(q)))]
        try:
            return _ReplicaRun(i, be, admitted, q, self.batch,
                               on_finish=self._on_finish(i),
                               guard=self._guard(be, i))
        except self.fault_types as e:
            self._on_fault(i, e, admitted + q, ladders)
            return None

    # -- serve --------------------------------------------------------------

    def serve(self, requests: list) -> list[dict]:
        """Admission-check the queue, place it on per-replica ladders, then
        drain every replica with interleaved per-replica wave dispatch (one
        step per replica per tick; each tick dispatches all replicas before
        collecting any, so split backends overlap their device work).
        Faulting replicas degrade and drain per the module docstring; the
        serve always returns — degraded service is structured refusals in
        ``self.outcomes``, not an exception."""
        requests = list(requests)
        with spans.top("scheduler.serve", requests=len(requests)):
            return self._serve(requests)

    def _serve(self, requests: list) -> list[dict]:
        self.outcomes = {}
        self._attempts = {}
        self._tick0 = self.waves
        with spans.span("scheduler.admit") as admit:
            admitted = _admit(self.backends[0], requests, self.outcomes,
                              max_queue=self.max_queue, wave=self.waves)
            admit.set(refused=len(requests) - len(admitted))
            if not self.live_replicas():
                for req in admitted:
                    self._refuse(req, "no_healthy_replicas")
                return []
            ladders = self._place(admitted)
        runs: list = [None] * self.replicas
        stats: list[dict] = []
        while True:
            self._expire(ladders, runs)
            for i in range(self.replicas):
                while self._live(i) and runs[i] is None:
                    q = self._claim(i, ladders, runs)
                    if q is None:
                        break
                    if not q:
                        continue
                    run = self._spawn(i, q, ladders)
                    if run is None:
                        continue
                    if run.drained():  # instant finish (e.g. max_new=1 LM)
                        self._retire(run, ladders, stats)
                    else:
                        runs[i] = run
            active = [r for r in runs if r is not None]
            if not active:
                # queued work with no live replica to run it: refuse it
                leftovers = [r for lad in ladders
                             for q in lad.values() for r in q]
                for req in leftovers:
                    self._refuse(req, "no_healthy_replicas")
                return stats
            self.waves += 1
            faulted: list = []
            for run in active:
                try:
                    run.dispatch(self.waves)
                except self.fault_types as e:
                    faulted.append((run, e))
            for i, run in enumerate(runs):
                if run is None:
                    continue
                exc = next((e for r, e in faulted if r is run), None)
                if exc is None:
                    try:
                        run.collect_and_deliver()
                    except self.fault_types as e:
                        exc = e
                if exc is not None:
                    runs[i] = None
                    self._on_fault(i, exc, run.in_flight() + run.queue,
                                   ladders)
                    continue
                if run.drained():
                    self._retire(run, ladders, stats)
                    runs[i] = None
