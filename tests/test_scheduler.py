"""Scheduler unit tests (no model): bucketing, retirement order, backfill
(including instant-finish chaining), can_backfill refusal, running one
wave ahead — plus the replica fleet: per-replica wave dispatch (a stalled
replica never blocks the others' retirement), least-loaded placement,
whole-wave work stealing, and N-replica output parity with one replica.

A scripted pure-python backend stands in for the model: each request
carries the emission stream its slot will produce, so slot lifecycle logic
is pinned independently of prefill/decode numerics.  Fleet backends share
an event log, so cross-replica interleaving is asserted directly.
"""
import dataclasses

import pytest

from repro.launch import spans
from repro.launch.scheduler import FleetScheduler


@dataclasses.dataclass
class Req:
    rid: int
    script: list            # emissions this request's slot produces, in order
    max_new: int
    out: list = dataclasses.field(default_factory=list)


class ScriptBackend:
    """Emits each request's scripted stream; finishes on eos or max_new."""

    def __init__(self, *, eos=None, len_bucket=4, admit=None):
        self.eos = eos
        self.len_bucket = len_bucket
        self.admit = admit or (lambda req: True)  # can_backfill predicate
        self.started = []                          # audit: admission waves

    def bucket_key(self, req):
        return -(-len(req.script) // self.len_bucket)

    def sort_key(self, req):
        return -len(req.script)

    def start(self, reqs, width):
        self.started.append([r.rid for r in reqs])
        state = {"cur": [None] * width}
        emis = [None] * width
        for j, r in enumerate(reqs):
            state["cur"][j] = iter(r.script)
            emis[j] = next(state["cur"][j])
        return state, emis

    def step(self, state, slots):
        return state, [next(state["cur"][j], 0) if r is not None else None
                       for j, r in enumerate(slots)]

    def can_backfill(self, state, req):
        return self.admit(req)

    def backfill(self, state, slot, req):
        state["cur"][slot] = iter(req.script)
        return state, next(state["cur"][slot])

    def append(self, req, e):
        req.out.append(e)
        if self.eos is not None and e == self.eos:
            return True
        return len(req.out) >= req.max_new

    def finish(self, state):
        return {"custom": 1}


class StepOnly:
    """Hides a backend's dispatch/collect split: the scheduler then runs
    the synchronous tick, collecting each wave before dispatching the
    next."""

    def __init__(self, inner):
        self.inner = inner

    def __getattr__(self, name):
        if name in ("dispatch", "collect"):
            raise AttributeError(name)
        return getattr(self.inner, name)


def _sched(be, batch):
    return FleetScheduler([be], batch=batch)


class TestLockstep:
    def test_exact_steps_no_trailing_step(self):
        """Uniform batch: start emits token 1, so max_new tokens need
        exactly max_new - 1 steps — the off-by-one regression pin."""
        be = ScriptBackend()
        reqs = [Req(i, list(range(10, 16)), 4) for i in range(2)]
        stats = _sched(be, 2).serve(reqs)
        assert len(stats) == 1
        s = stats[0]
        assert s["steps"] == 3
        assert s["emissions"] == 8 and s["finished"] == 2
        assert all(r.out == [10, 11, 12, 13] for r in reqs)
        assert s["custom"] == 1  # backend.finish merged in

    def test_retired_slot_backfilled_same_run(self):
        """A short sequence frees its slot for a queued request within the
        same run."""
        be = ScriptBackend()
        reqs = [Req(0, [1] * 8, 2), Req(1, [2] * 8, 6), Req(2, [3] * 8, 3)]
        stats = _sched(be, 2).serve(reqs)
        assert len(stats) == 1
        s = stats[0]
        assert s["backfills"] == 1 and s["finished"] == 3
        assert [len(r.out) for r in reqs] == [2, 6, 3]
        # r0 retires after step 1; r2 rides its slot; the run is bounded by
        # the longest request: 6 tokens -> 5 steps
        assert s["steps"] == 5
        assert s["emissions"] == 11

    def test_eos_retires_early(self):
        be = ScriptBackend(eos=99)
        r = Req(0, [5, 99, 7, 7], 4)
        stats = _sched(be, 1).serve([r])
        assert r.out == [5, 99]          # eos recorded, then retired
        assert stats[0]["steps"] == 1    # no steps wasted past the eos

    def test_backfill_chain_instant_finish(self):
        """A backfilled max_new=1 request finishes on its admission emission
        and must chain straight into the next backfill."""
        be = ScriptBackend()
        reqs = [Req(0, [1, 1], 2), Req(1, [2], 1), Req(2, [3, 3], 2)]
        stats = _sched(be, 1).serve(reqs)
        assert len(stats) == 1
        s = stats[0]
        assert s["backfills"] == 2 and s["finished"] == 3
        assert reqs[1].out == [2] and reqs[2].out == [3, 3]
        assert s["steps"] == 2  # r0: 1 step; r2: 1 step; r1 rides admissions

    def test_bucketing_splits_and_sorts(self):
        """Different buckets never share a run; within a bucket the sort key
        (longest first) picks the admission order."""
        be = ScriptBackend(len_bucket=4)
        short = [Req(0, [1] * 3, 2), Req(1, [1] * 4, 2)]   # bucket 1
        long = [Req(2, [1] * 8, 2), Req(3, [1] * 7, 2)]    # bucket 2
        stats = _sched(be, 2).serve([short[0], long[0], short[1], long[1]])
        assert len(stats) == 2
        assert be.started == [[1, 0], [2, 3]]

    def test_can_backfill_refusal_spills_to_new_run(self):
        """A request the backend refuses mid-run gets a fresh run
        instead of being dropped."""
        be = ScriptBackend(admit=lambda req: req.rid != 2)
        reqs = [Req(0, [1] * 4, 2), Req(1, [2] * 4, 2), Req(2, [3] * 4, 2)]
        stats = _sched(be, 2).serve(reqs)
        assert len(stats) == 2
        assert stats[0]["backfills"] == 0
        assert [len(r.out) for r in reqs] == [2, 2, 2]
        assert be.started == [[0, 1], [2]]

    def test_first_fit_skips_refused_head(self):
        """If the queue head doesn't fit, a later request that does is
        backfilled (first-fit scan)."""
        be = ScriptBackend(admit=lambda req: req.rid != 2)
        # sort_key keeps scripted lengths equal so queue order is stable
        reqs = [Req(0, [1] * 4, 1), Req(1, [2] * 4, 4),
                Req(2, [3] * 4, 2), Req(3, [4] * 4, 2)]
        stats = _sched(be, 2).serve(reqs)
        assert len(stats) == 2
        assert stats[0]["backfills"] == 1
        assert be.started == [[0, 1], [2]]
        assert len(reqs[3].out) == 2     # rid 3 rode rid 0's slot


class OneShotBackend:
    """A one-shot backend with the dispatch/collect split, like the CNN
    one: each request finishes on its wave's emission (``10 * rid``), and
    every call is logged.  ``one_shot = False`` on an instance drives the
    same backend through the synchronous tick."""

    one_shot = True

    def __init__(self, admit=None):
        self.admit = admit or (lambda req: True)
        self.log = []                      # ("dispatch"|"collect", wave)
        self.waves = []                    # rids of each dispatched wave
        self.delivered = []                # rids in append order
        self.in_flight = self.most_in_flight = 0

    def bucket_key(self, req):
        return 0

    def sort_key(self, req):
        return req.rid

    def start(self, reqs, width):
        return {}, None

    def dispatch(self, state, slots):
        k = len(self.waves)
        self.waves.append([r.rid if r is not None else None for r in slots])
        self.log.append(("dispatch", k))
        self.in_flight += 1
        self.most_in_flight = max(self.most_in_flight, self.in_flight)
        return k

    def collect(self, state, k, slots):
        self.log.append(("collect", k))
        self.in_flight -= 1
        return state, [10 * rid if rid is not None else None
                       for rid in self.waves[k]]

    def step(self, state, slots):
        return self.collect(state, self.dispatch(state, slots), slots)

    def can_backfill(self, state, req):
        return self.admit(req)

    def backfill(self, state, slot, req):
        return state, None

    def append(self, req, e):
        req.out.append(e)
        self.delivered.append(req.rid)
        return True

    def finish(self, state):
        return {}


def _one_shot(n, batch, *, one_shot=True, admit=None):
    be = OneShotBackend(admit)
    be.one_shot = one_shot
    sched = FleetScheduler([be], batch=batch)
    reqs = [Req(i, [], 1) for i in range(n)]
    stats = sched.serve(reqs)
    return be, sched, reqs, stats


class TestRunAhead:
    def test_next_wave_dispatched_before_current_collected(self):
        """Four full waves: wave k+1 is dispatched before wave k is
        collected, and never more than two waves are in flight."""
        be, _, reqs, stats = _one_shot(8, 2)
        assert be.waves == [[0, 1], [2, 3], [4, 5], [6, 7]]
        assert be.log == [("dispatch", 0), ("dispatch", 1), ("collect", 0),
                          ("dispatch", 2), ("collect", 1),
                          ("dispatch", 3), ("collect", 2), ("collect", 3)]
        assert be.most_in_flight == 2
        assert [r.out for r in reqs] == [[10 * i] for i in range(8)]

    # (requests, batch, can_backfill): 4 full waves, a partial tail wave,
    # one request, a refused request that spills to a fresh run
    @pytest.mark.parametrize("n,batch,admit", [
        (8, 2, None), (7, 4, None), (1, 4, None), (13, 4, None),
        (6, 2, lambda req: req.rid != 3),
    ])
    def test_same_waves_stats_and_outcomes_as_step(self, n, batch, admit):
        """Run-ahead changes only when a wave is collected: its batches,
        stats, outcomes and delivery order are those of the same backend
        driven through ``step``."""
        ahead, a_sched, a_reqs, a_stats = _one_shot(n, batch, admit=admit)
        step, s_sched, s_reqs, s_stats = _one_shot(n, batch, admit=admit,
                                                   one_shot=False)
        assert step.most_in_flight == 1
        assert ahead.waves == step.waves
        assert ahead.delivered == step.delivered
        assert [r.out for r in a_reqs] == [r.out for r in s_reqs]
        keys = ("steps", "finished", "backfills", "emissions")
        assert [{k: s[k] for k in keys} for s in a_stats] == \
            [{k: s[k] for k in keys} for s in s_stats]
        assert a_sched.outcomes == s_sched.outcomes
        assert a_sched.waves == s_sched.waves == len(step.waves)

    def test_one_request_is_one_wave(self):
        be, _, reqs, stats = _one_shot(1, 4)
        assert be.log == [("dispatch", 0), ("collect", 0)]
        assert be.waves == [[0, None, None, None]]
        assert stats[0]["steps"] == 1 and stats[0]["backfills"] == 0
        assert reqs[0].outcome.wave == 1

    def test_partial_tail_wave(self):
        """Seven requests at width 4: the tail wave holds the three
        backfills and an idle lane, dispatched while the full wave runs."""
        be, _, reqs, stats = _one_shot(7, 4)
        assert be.waves == [[0, 1, 2, 3], [4, 5, 6, None]]
        assert be.log == [("dispatch", 0), ("dispatch", 1), ("collect", 0),
                          ("collect", 1)]
        assert stats[0]["backfills"] == 3 and stats[0]["steps"] == 2
        assert [r.outcome.wave for r in reqs] == [1] * 4 + [2] * 3


class FleetOneShot(OneShotBackend):
    """One fleet replica's one-shot backend; all replicas share
    ``events``, so the order of dispatches and collects across replicas
    is observable."""

    def __init__(self, replica, events, **kw):
        super().__init__(**kw)
        self.replica = replica
        self.events = events

    def dispatch(self, state, slots):
        k = super().dispatch(state, slots)
        self.events.append(("dispatch", self.replica, k))
        return k

    def collect(self, state, k, slots):
        self.events.append(("collect", self.replica, k))
        return super().collect(state, k, slots)


class TestFleetRunAhead:
    def test_each_replica_runs_one_wave_ahead(self, monkeypatch):
        """Two replicas, three waves each: every tick dispatches on both
        replicas before it collects on either, each replica dispatches
        wave k+1 before it collects wave k (``ahead`` 0, 1, 1), and no
        replica ever has more than two waves in flight."""
        monkeypatch.setattr(spans, "profiler_active", lambda: True)
        events = []
        bes = [FleetOneShot(i, events) for i in range(2)]
        sched = FleetScheduler(bes, batch=2)
        reqs = [Req(i, [], 1) for i in range(12)]
        spans.clear()
        sched.serve(reqs)
        rec = spans.recorded()
        spans.clear()
        d = lambda i, k: ("dispatch", i, k)
        c = lambda i, k: ("collect", i, k)
        assert events == [d(0, 0), d(1, 0),
                          d(0, 1), d(1, 1), c(0, 0), c(1, 0),
                          d(0, 2), d(1, 2), c(0, 1), c(0, 2),
                          c(1, 1), c(1, 2)]
        assert [be.most_in_flight for be in bes] == [2, 2]
        assert bes[0].waves == [[0, 1], [4, 5], [8, 9]]
        assert bes[1].waves == [[2, 3], [6, 7], [10, 11]]
        waves = [s for s in rec if s.name == "backend.wave"]
        for i in range(2):
            mine = sorted((s.attrs["wave"], s.attrs["ahead"]) for s in waves
                          if s.attrs["replica"] == i)
            assert mine == [(1, 0), (2, 1), (3, 1)]
        assert [r.out for r in reqs] == [[10 * i] for i in range(12)]
        assert [r.outcome.replica for r in reqs] == [0, 0, 1, 1] * 3
        assert [r.outcome.wave for r in reqs] == [1] * 4 + [2] * 4 + [3] * 4

    # (queue length, rids stolen) at batch 2: w waves leave floor(w/2)
    @pytest.mark.parametrize("n,stolen", [
        (1, [0]), (2, [0, 1]), (3, [2]), (4, [2, 3]), (5, [2, 3, 4]),
        (6, [2, 3, 4, 5]),
    ])
    def test_claim_steals_whole_waves(self, n, stolen):
        """An idle replica takes whole waves from the tail of the longest
        queue, so no wave is split into narrower ones."""
        sched = FleetScheduler([OneShotBackend(), OneShotBackend()],
                               batch=2)
        victim = [Req(i, [], 1) for i in range(n)]
        ladders = [{}, {0: victim}]
        got = sched._claim(0, ladders, [None, None])
        assert [r.rid for r in got] == stolen
        assert [r.rid for r in victim] == [i for i in range(n)
                                           if i not in stolen]
        assert sched.steals == 1


class FleetScript(ScriptBackend):
    """One fleet replica's scripted backend; all replicas share ``events``
    so cross-replica ordering is observable."""

    def __init__(self, replica, events, **kw):
        super().__init__(**kw)
        self.replica = replica
        self.events = events

    def start(self, reqs, width):
        self.events.append(("start", self.replica, [r.rid for r in reqs]))
        return super().start(reqs, width)

    def step(self, state, slots):
        self.events.append(("step", self.replica))
        return super().step(state, slots)

    def backfill(self, state, slot, req):
        self.events.append(("backfill", self.replica, req.rid))
        return super().backfill(state, slot, req)


def _fleet(n, batch, **kw):
    events = []
    bes = [FleetScript(i, events, **kw) for i in range(n)]
    return FleetScheduler(bes, batch=batch), bes, events


class TestFleet:
    def test_stalled_replica_never_blocks_retirement_and_steal(self):
        """The headline fleet property: replica 0 grinds a 10-step wave
        while replica 1 retires its own waves AND steals replica 0's
        queued straggler — nothing waits on the slow wave."""
        sched, bes, events = _fleet(2, 1)
        a = Req(0, [9] * 10, 10)                 # 10 emissions: 9 steps
        b, c, d = (Req(i, [i], 1) for i in (1, 2, 3))
        # chunk placement (batch=1, least-loaded): a->r0, b->r1, c->r0, d->r1
        stats = sched.serve([a, b, c, d])
        assert len(a.out) == 10
        assert all(len(r.out) == 1 for r in (b, c, d))
        # c was queued behind a on replica 0 and moved to idle replica 1
        assert sched.steals == 1
        assert ("start", 1, [2]) in events
        # every replica-1 event precedes replica 0's first step: the slow
        # wave never gated the fast replica's retirement
        first_r0_step = events.index(("step", 0))
        assert all(e[1] == 0 for e in events[first_r0_step:])
        # retirement order: both replica-1 runs retire before replica 0's
        assert [s["replica"] for s in stats] == [1, 1, 0]
        assert stats[-1]["steps"] == 9 and stats[-1]["finished"] == 1

    def test_per_replica_wave_dispatch_interleaves(self):
        """Two busy replicas advance one step per tick each — interleaved,
        not drained sequentially."""
        sched, bes, events = _fleet(2, 2)
        reqs = [Req(i, [i] * 4, 4) for i in range(4)]
        sched.serve(reqs)
        steps = [e[1] for e in events if e[0] == "step"]
        assert steps == [0, 1, 0, 1, 0, 1]       # 3 ticks, both replicas
        assert all(len(r.out) == 4 for r in reqs)

    def test_least_loaded_chunk_placement(self):
        """Wave-sized chunks land on the least-loaded replica, ties to the
        lowest index."""
        sched, bes, events = _fleet(3, 2)
        reqs = [Req(i, [i] * 2, 2) for i in range(10)]   # 5 chunks of 2
        sched.serve(reqs)
        waves = {e[1]: e[2] for e in events if e[0] == "start"}
        assert waves[0] == [0, 1] and waves[1] == [2, 3] and \
            waves[2] == [4, 5]
        # chunks 4 and 5 backfill replicas 0 and 1's runs (same bucket)
        assert all(len(r.out) == 2 for r in reqs)
        assert sched.steals == 0

    def test_fleet_of_one_matches_lockstep(self):
        """One replica running ahead reproduces the synchronous tick of
        the same backend behind a wrapper that hides dispatch/collect:
        batches, stats, outputs and outcomes."""
        def run(wrap):
            be = OneShotBackend()
            sched = FleetScheduler([wrap(be)], batch=2)
            reqs = [Req(i, [], 1) for i in range(5)]
            stats = sched.serve(reqs)
            return be, sched, reqs, stats
        ahead, a_sched, a_reqs, a_stats = run(lambda be: be)
        step, s_sched, s_reqs, s_stats = run(StepOnly)
        assert ahead.most_in_flight == 2 and step.most_in_flight == 1
        assert ahead.waves == step.waves == [[0, 1], [2, 3], [4, None]]
        assert [r.out for r in a_reqs] == [r.out for r in s_reqs]
        keys = ("steps", "finished", "backfills", "emissions")
        assert [{k: s[k] for k in keys} for s in a_stats] == \
            [{k: s[k] for k in keys} for s in s_stats]
        assert a_sched.outcomes == s_sched.outcomes

    def test_n_replica_outputs_match_single(self):
        """Every request's emission stream is identical however many
        replicas serve the queue (the fleet analogue of the CNN
        bit-identity gate, scripted)."""
        def serve(n):
            reqs = [Req(i, [10 + i] * 6, 1 + i % 4) for i in range(12)]
            sched, _, _ = _fleet(n, 2)
            sched.serve(reqs)
            return [r.out for r in reqs]
        ref = serve(1)
        for n in (2, 3, 5):
            assert serve(n) == ref

    def test_leftover_queue_gets_fresh_run(self):
        """Requests a backend refuses to backfill are not lost on the
        fleet path: they get a fresh run on their replica."""
        sched, bes, events = _fleet(1, 2,
                                    admit=lambda req: req.rid != 2)
        reqs = [Req(0, [1] * 4, 2), Req(1, [2] * 4, 2), Req(2, [3] * 4, 2)]
        stats = sched.serve(reqs)
        assert [len(r.out) for r in reqs] == [2, 2, 2]
        assert len(stats) == 2
        assert bes[0].started == [[0, 1], [2]]
