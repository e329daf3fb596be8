"""The serving loop: queue, batch bucketing, slot retirement, backfill,
one wave ahead per replica, and fleet-level fault tolerance.

The scheduler owns *when* things run — admission, bucketing requests that
may share a batch, the slot lifecycle (live -> retired -> backfilled),
placement on replicas — and a backend owns *what* runs (the model math).
The LM prefill/decode stack and the CNN `SparseNet.apply` path both plug in
here (`launch.serve.LMBackend` / `launch.serve.CNNBackend`).  There is one
loop, `FleetScheduler`; a single backend is a fleet of one.

Backend protocol (duck-typed)
-----------------------------
  bucket_key(req) -> hashable
      Requests sharing a key may share a batch (LM: prompt-length bucket;
      CNN: padded image shape).
  sort_key(req) -> sortable
      Admission order within a bucket (LM: longest prompt first, so every
      later backfill fits the already-grown context).
  context() -> context manager          (optional)
      Entered around each call into the backend (mesh/sharding scope).
  start(reqs, width) -> (state, emissions | None)
      Admit the first wave into a width-slot batch (LM: prefill, emitting
      each slot's first token; CNN: nothing to emit before the first wave).
  step(state, slots) -> (state, emissions)
      One wave over all slots; ``slots`` is the width-long list of
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

Optional protocol extensions:

  dispatch(state, slots) -> handle
  collect(state, handle, slots) -> (state, emissions)
      ``step`` split in two: ``dispatch`` issues the wave and returns
      without blocking on its result, ``collect`` blocks and emits.
  one_shot = True
      Every request finishes on the first emission of the wave that
      computes it (``append`` returns True), ``backfill`` emits nothing,
      and neither ``can_backfill`` nor ``backfill`` reads the emissions of
      a wave still in flight.
  validate_request(req) -> str | None
      Admission-time validation: a refusal reason string rejects the
      request with a structured `RequestOutcome` *before* it can cause a
      mid-wave shape/dtype error; None admits it.
  check_emission(emission) -> bool
      Output guard: False means the emission is corrupt (e.g. non-finite
      logits); the replica is quarantined and the wave re-served elsewhere.
  reset(req) -> None
      Clear a request's partial progress before it is re-served after a
      replica fault.  Backends without ``reset`` get partially-delivered
      requests refused (``partial_stream_lost``) rather than duplicated.

Runs and waves
--------------
A run admits up to ``batch`` requests of one bucket and steps until every
slot is idle.  A finished request frees its slot *immediately*: the
delivery pass scans the run's queue first-fit and backfills, chaining if
the newcomer itself finishes on its admission emission (e.g. ``max_new=1``).
Requests the backend refused to backfill (capacity, context length) get a
fresh run of their own.

A one-shot backend with the dispatch/collect split runs one wave ahead:
wave k+1 (the requests the delivery pass would backfill into the slots
wave k frees, in the same order) is dispatched before wave k is collected,
so its host work overlaps wave k's device work; at most two waves are in
flight per replica, and a wave is collected with nothing left to backfill.
Batches, counts and outcomes are those of the synchronous tick (``step``
or dispatch-then-collect, deliver, backfill) that every other backend
keeps.

Replica fleet
-------------
Admission cuts each bucket's sorted queue into wave-sized chunks placed on
the least-loaded replica's bucket ladder (ties to the lowest index, so one
replica admits in plain bucket order).  Each loop tick advances every
replica's run by one wave, dispatching on every replica before collecting
on any, so a slow wave on replica 0 never stalls retirement or backfill on
the others and split backends overlap their device work.  An idle replica
*steals* whole waves from the longest queue still waiting on another
replica (a pending ladder queue, or the queued remainder behind an
in-flight run): of a queue of w waves it leaves the first floor(w/2) full
waves and takes the rest, so a stolen request runs at the width it would
have run at where it was.

Fault tolerance
---------------
Every backend call is guarded by the typed `launch.faults.FAULT_TYPES`
hierarchy (never a blanket ``except`` — vscheck VSC304).  Replica health
walks ``healthy -> suspect -> quarantined -> drained``:

  * a transient fault marks the replica *suspect* and re-queues its waves;
    ``suspect_limit`` transients quarantine it;
  * a non-transient fault (`ReplicaDead`, `CompileFault`, the
    `NonFiniteOutput` raised by the output guard) quarantines immediately;
  * the requests of every uncollected wave, and the run's queue, are
    re-placed on the surviving replicas; quarantine also moves the
    replica's pending ladder, then marks it *drained* (terminal).  No
    request is lost and nothing that reached ``append`` is served again.

Per-request budgets are counted in loop ticks, never the clock:
``deadline_waves`` refuses a request still *queued* after that many ticks,
``max_attempts`` bounds fault-driven re-placements.  Bounded admission
(``max_queue``) sheds load at serve() entry.  Every admitted request ends
in exactly one terminal `RequestOutcome` — delivered, or a structured
refusal (``queue_full``, ``invalid:*``, ``deadline_exceeded``,
``retry_budget_exhausted``, ``no_healthy_replicas``,
``partial_stream_lost``) — and control flow stays clock-free, so a faulty
run (chaos-injected or real) is exactly replayable.

Spans
-----
While a JAX profiler session is on, each ``serve`` call records
`launch.spans`: ``scheduler.serve`` (``requests``), ``scheduler.admit``
(admission, bucketing and placement; ``refused``), ``scheduler.run`` (one
run; ``replica``), ``scheduler.deliver`` (one delivery pass) and
``backend.wave`` (one wave, dispatch to collect; ``wave``, ``replica``,
``ahead``: 1 when the wave was dispatched while the replica's wave before
it was still in flight, else 0).  The clock readings of ``scheduler.run``
are the run stats' ``start_s``/``run_s``; off a profiler session they are
the only clock reads.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time

from repro.launch import spans
from repro.launch.faults import FAULT_TYPES, NonFiniteOutput

__all__ = ["FleetScheduler", "RequestOutcome",
           "HEALTHY", "SUSPECT", "QUARANTINED", "DRAINED"]


# replica health states: healthy -> suspect -> quarantined -> drained
HEALTHY = "healthy"
SUSPECT = "suspect"
QUARANTINED = "quarantined"
DRAINED = "drained"


@dataclasses.dataclass
class RequestOutcome:
    """The single terminal outcome of one admitted request.

    ``status`` is ``"delivered"`` or ``"refused"``; refusals carry a
    machine-readable ``reason``.  ``wave`` is the loop tick the outcome
    was decided at: for a delivered request, the ``wave`` of the
    ``backend.wave`` span that computed it.  ``attempts`` counts
    fault-driven re-placements the request survived before its outcome.
    """

    rid: object
    status: str
    reason: str | None = None
    replica: int | None = None
    attempts: int = 0
    wave: int = 0


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
    """Admission control: validate each request through the backend's
    optional ``validate_request`` and shed load beyond ``max_queue``.
    Refused requests get a structured `RequestOutcome`; the admitted
    remainder is returned in order."""
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


class _ReplicaRun:
    """One resumable in-flight run on one replica.

    Start + deliver on construction (the caller pops the admission wave so
    a failing ``start`` can re-queue it), then one `dispatch` and one
    `collect` per loop tick until `drained`.  ``slots`` holds the next
    wave to dispatch; ``flights`` the waves dispatched and not yet
    collected, oldest first — at most one, or two for a one-shot backend
    with the dispatch/collect split, which runs one wave ahead (module
    docstring).  ``on_finish(req, replica, wave)`` is called once per
    retired request.  ``guard`` (optional) validates each wave's emissions
    before delivery; it raises to reject the whole wave, so corrupt
    emissions are never appended.  The ``scheduler.run`` span lasts from
    construction to `finish`, and is the parent of the run's waves and
    deliveries.
    """

    def __init__(self, replica: int, be, admitted: list, queue: list,
                 width: int, *, wave: int, on_finish, guard=None):
        self.replica = replica
        self.be = be
        self.queue = queue
        self.on_finish = on_finish
        self.guard = guard
        self._ctx = getattr(be, "context", None) or contextlib.nullcontext
        self.split = hasattr(be, "dispatch")
        self.ahead = self.split and getattr(be, "one_shot", False)
        self.slots: list = admitted + [None] * (width - len(admitted))
        self.flights: list = []          # (wave, span, slots, handle)
        self.steps = self.finished = self.backfills = self.emitted = 0
        with self._ctx():
            t0 = time.time_ns()
            self._span = spans.begin("scheduler.run", t0, replica=replica)
            with self._span:
                self.state, emis = be.start(admitted, width)
                self._t1 = time.time_ns()
                self.start_s = (self._t1 - t0) / 1e9
                self._deliver(self.slots, self.queue, emis, wave)

    def _deliver(self, slots, queue, emis, wave: int):
        """One delivery pass over ``slots`` (mutated in place): append
        emissions, retire finished requests, first-fit backfill from
        ``queue`` (consumed in place), chaining when a backfilled request
        finishes on its admission emission."""
        if self.guard is not None and emis is not None:
            self.guard(emis)
        be = self.be
        with spans.span("scheduler.deliver"):
            for j in range(len(slots)):
                req = slots[j]
                e = None if emis is None else emis[j]
                while req is not None and e is not None:
                    done = be.append(req, e)
                    self.emitted += 1
                    e = None
                    if not done:
                        break
                    self.finished += 1
                    self.on_finish(req, self.replica, wave)
                    self.state, req, e = _backfill(be, self.state, queue, j)
                    self.backfills += req is not None
                slots[j] = req

    def drained(self) -> bool:
        return not self.flights and all(s is None for s in self.slots)

    def in_flight(self) -> list:
        """The requests of every uncollected wave and of the next one,
        oldest first (for fault re-placement)."""
        waves = [f[2] for f in self.flights if f[2] is not self.slots]
        return [r for w in waves + [self.slots] for r in w if r is not None]

    def dispatch(self, wave: int):
        """Issue ``slots`` as this replica's wave of loop tick ``wave``; a
        backend with the split returns without blocking on the result.
        Running ahead, then take the next wave from the queue: the
        requests the delivery pass would backfill into the slots this wave
        frees."""
        be = self.be
        with self._ctx(), self._span:
            span = spans.begin("backend.wave", wave=wave,
                               replica=self.replica,
                               ahead=int(bool(self.flights)))
            with span:
                if self.split:
                    handle = be.dispatch(self.state, self.slots)
                else:
                    handle = be.step(self.state, self.slots)
            self.steps += 1
            self.flights.append((wave, span, self.slots, handle))
            if self.ahead:
                nxt = [None] * len(self.slots)
                for j, req in enumerate(self.slots):
                    if req is not None:
                        self.state, nxt[j], _ = _backfill(
                            be, self.state, self.queue, j)
                self.backfills += sum(r is not None for r in nxt)
                self.slots = nxt

    def collect(self):
        """Collect and deliver this tick's waves, oldest first: every wave
        in flight but the one just dispatched, and that one too unless a
        wave follows it.  Running ahead, a wave's collect backfills
        nothing (its successor is already taken), and a request it left
        unfinished (a stalled wave) goes back to the head of the queue."""
        keep = int(self.ahead and any(s is not None for s in self.slots))
        with self._ctx(), self._span:
            while len(self.flights) > keep:
                wave, span, slots, handle = self.flights[0]
                with span:
                    if self.split:
                        self.state, emis = self.be.collect(
                            self.state, handle, slots)
                    else:
                        self.state, emis = handle
                span.end()
                self._deliver(slots, [] if self.ahead else self.queue,
                              emis, wave)
                del self.flights[0]
                if self.ahead:
                    self.queue[:0] = [r for r in slots if r is not None]

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
    """The serving loop over N backend replicas (N >= 1): per-replica
    waves, replica health tracking and fault-driven re-placement.

    ``backends`` hold the same model behind the backend protocol, one
    weight copy each (see module docstring).  ``serve`` returns one stats
    dict per run, tagged with the ``replica`` that ran it; ``steals``
    counts queues moved between replicas since construction.  Fault
    handling (the module docstring's *Fault tolerance* section) is
    configured by:

      fault_types     exception types treated as replica faults (default
                      `launch.faults.FAULT_TYPES`); anything else
                      propagates — a bug should still fail fast;
      suspect_limit   transient faults a replica survives before
                      quarantine;
      max_attempts    fault-driven re-placements one request survives
                      before a ``retry_budget_exhausted`` refusal;
      deadline_waves  default per-request deadline in loop ticks (a
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
        self.waves = 0                       # loop ticks since construction
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
        queued request count; ties to the lowest index)."""
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
        whole waves from the longest queue still waiting on any other
        replica — a pending ladder queue, or the *queued* remainder of an
        in-flight run's backfill source (admitted slots never move).  Of a
        queue of w waves of ``batch`` the victim keeps the first
        floor(w/2) full waves; the rest moves, so a lone straggler moves
        too and no wave is split."""
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
        keep = -(-len(victim) // self.batch) // 2 * self.batch
        stolen = victim[keep:]
        del victim[keep:]
        self.steals += 1
        return stolen

    def _retire(self, run, ladders: list[dict], stats: list[dict]):
        """Record a drained run; leftover queued requests its backend
        refused to backfill go back on the replica's ladder for a fresh
        run."""
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

    def _delivered(self, req, replica: int, wave: int) -> None:
        _record(self.outcomes, req, RequestOutcome(
            rid=getattr(req, "rid", None), status="delivered",
            replica=replica, attempts=self._attempts.get(id(req), 0),
            wave=wave))

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
        """Deadline sweep: refuse requests still *queued* (not in a wave)
        after their tick budget.  ``deadline_waves`` counts loop ticks
        since this serve() started; requests in a wave always complete."""
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
        admitted = q[: self.batch]
        del q[: self.batch]
        try:
            return _ReplicaRun(i, be, admitted, q, self.batch,
                               wave=self.waves, on_finish=self._delivered,
                               guard=self._guard(be, i))
        except self.fault_types as e:
            self._on_fault(i, e, admitted + q, ladders)
            return None

    # -- serve --------------------------------------------------------------

    def run_once(self, queue: list) -> dict:
        """One run on replica 0 to its end: admit up to ``batch`` requests
        of ``queue`` and step until every slot retires, backfilling from
        ``queue`` (consumed in place; what the backend refused to backfill
        stays in it).  No admission control, output guard or fault
        handling: a fault propagates.  Returns the run's stats."""
        assert queue, "run_once needs at least one request"
        admitted = queue[: self.batch]
        del queue[: self.batch]
        run = _ReplicaRun(0, self.backends[0], admitted, queue, self.batch,
                          wave=self.waves, on_finish=self._delivered)
        while not run.drained():
            self.waves += 1
            run.dispatch(self.waves)
            run.collect()
        return run.finish()

    def serve(self, requests: list) -> list[dict]:
        """Admission-check the queue, place it on per-replica ladders, then
        drain every replica one wave per tick (each tick dispatches on all
        replicas before collecting on any).  Faulting replicas degrade and
        drain per the module docstring; the serve always returns —
        degraded service is structured refusals in ``self.outcomes``, not
        an exception."""
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
            if all(r is None for r in runs):
                # queued work with no live replica to run it: refuse it
                leftovers = [r for lad in ladders
                             for q in lad.values() for r in q]
                for req in leftovers:
                    self._refuse(req, "no_healthy_replicas")
                return stats
            self.waves += 1
            faulted: dict = {}
            for i, run in enumerate(runs):
                if run is not None:
                    try:
                        run.dispatch(self.waves)
                    except self.fault_types as e:
                        faulted[i] = e
            for i, run in enumerate(runs):
                if run is None:
                    continue
                exc = faulted.get(i)
                if exc is None:
                    try:
                        run.collect()
                    except self.fault_types as e:
                        exc = e
                if exc is not None:
                    runs[i] = None
                    self._on_fault(i, exc, run.in_flight() + run.queue,
                                   ladders)
                elif run.drained():
                    self._retire(run, ladders, stats)
                    runs[i] = None
