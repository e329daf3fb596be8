"""Fault-tolerant fleet serving: deterministic chaos injection, replica
health states (healthy -> suspect -> quarantined -> drained), re-placement
without loss or duplication, deadlines, retry budgets and admission
control.

Scripted-backend tests (no jax) pin the scheduler's fault handling
exactly; the real-CNN sweep at the bottom is the acceptance gate — a
replica death injected at every (replica, wave, kind) schedule position of
a 3-replica fleet still yields logits bit-identical to the fault-free
fleet.
"""
import dataclasses

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st
from test_scheduler import (
    FleetOneShot, FleetScript, OneShotBackend, Req, ScriptBackend, StepOnly,
)

from repro.launch.faults import (
    ChaosBackend, CompileFault, Fault, FaultPlan, ReplicaDead,
)
from repro.launch.scheduler import (
    DRAINED, HEALTHY, QUARANTINED, SUSPECT, FleetScheduler,
)


class ResetScript(ScriptBackend):
    """ScriptBackend + the ``reset`` hook: a fault-displaced request's
    partial stream is cleared and regenerates identically (the script is
    re-iterated from the top)."""

    def reset(self, req):
        req.out.clear()


def _chaos_fleet(n, batch, plan, *, be_cls=FleetScript, sched_kw=None,
                 **kw):
    events = []
    bes = [ChaosBackend(be_cls(i, events, **kw), plan, replica=i)
           for i in range(n)]
    sched = FleetScheduler(bes, batch=batch, **(sched_kw or {}))
    return sched, bes, events


class ResetFleetScript(FleetScript):
    def reset(self, req):
        req.out.clear()


def _mk_reqs(n=6, script_len=4, max_new=2):
    return [Req(i, [(i + 1) * 10 + k for k in range(script_len)], max_new)
            for i in range(n)]


def _check_terminal(sched, reqs):
    """Every admitted request has exactly one terminal outcome, and
    delivered streams are never duplicated."""
    assert set(sched.outcomes) == {r.rid for r in reqs}
    for r in reqs:
        o = sched.outcomes[r.rid]
        assert o is r.outcome
        assert o.status in ("delivered", "refused")
        if o.status == "delivered":
            want = min(len(r.script), r.max_new)
            assert r.out == r.script[:want], (r.rid, r.out)
        else:
            assert isinstance(o.reason, str) and o.reason


class TestPlan:
    def test_seeded_plan_is_deterministic(self):
        a = FaultPlan.random(7, replicas=3)
        b = FaultPlan.random(7, replicas=3)
        assert a.faults == b.faults
        assert FaultPlan.random(8, replicas=3).faults != a.faults

    def test_plan_indexing_and_counts(self):
        plan = FaultPlan([Fault("nan", 0, 2), Fault("stall", 0, 2, ticks=3),
                          Fault("transient", 1, 0)])
        assert [f.kind for f in plan.at(0, 2)] == ["nan", "stall"]
        assert plan.at(2, 0) == []
        assert plan.counts() == {"nan": 1, "stall": 1, "transient": 1}
        assert len(plan) == 3

    def test_invalid_faults_rejected(self):
        with pytest.raises(ValueError, match="unknown fault kind"):
            Fault("explode", 0, 0)
        with pytest.raises(ValueError, match="invalid fault"):
            Fault("nan", 0, -1)


class TestChaosTransparency:
    def test_empty_plan_fleet_of_one_matches_lockstep(self):
        """The invariant everything else builds on: one chaos-wrapped
        replica with an empty plan, running one wave ahead, is
        bit-identical to the same backend on the synchronous tick —
        batches, stats, outputs, outcomes."""
        def run(wrap):
            be = OneShotBackend()
            sched = FleetScheduler([wrap(be)], batch=2)
            reqs = [Req(i, [], 1) for i in range(7)]
            stats = sched.serve(reqs)
            return be, sched, reqs, stats
        chaos, c_sched, c_reqs, c_stats = run(
            lambda be: ChaosBackend(be, FaultPlan(), replica=0))
        step, s_sched, s_reqs, s_stats = run(StepOnly)
        assert chaos.most_in_flight == 2 and step.most_in_flight == 1
        assert chaos.waves == step.waves
        assert [r.out for r in c_reqs] == [r.out for r in s_reqs]
        keys = ("steps", "finished", "backfills", "emissions")
        assert [{k: s[k] for k in keys} for s in c_stats] == \
            [{k: s[k] for k in keys} for s in s_stats]
        assert c_sched.health == [HEALTHY] and c_sched.fault_events == []
        assert c_sched.outcomes == s_sched.outcomes

    def test_empty_plan_never_fires(self):
        sched, bes, _ = _chaos_fleet(2, 2, FaultPlan())
        reqs = _mk_reqs(8)
        sched.serve(reqs)
        _check_terminal(sched, reqs)
        assert all(be.injected == [] for be in bes)


class TestReplicaDeath:
    def test_die_dispatch_requeues_on_survivor(self):
        """Replica 0 dies dispatching its first wave: its in-flight slots
        and pending ladder move to replica 1; nothing is lost, nothing
        delivered twice."""
        plan = FaultPlan([Fault("die_dispatch", 0, 1)])
        sched, bes, _ = _chaos_fleet(2, 2, plan,
                                     be_cls=ResetFleetScript)
        reqs = _mk_reqs(8, script_len=4, max_new=3)
        sched.serve(reqs)
        assert sched.health == [DRAINED, HEALTHY]
        assert [e["fault"] for e in sched.fault_events] == ["ReplicaDead"]
        _check_terminal(sched, reqs)
        assert all(o.status == "delivered"
                   for o in sched.outcomes.values())
        # everything after the death ran on replica 1
        assert all(o.replica == 1 for o in sched.outcomes.values()
                   if o.wave > sched.fault_events[0]["wave"])

    def test_die_collect_loses_no_request(self):
        plan = FaultPlan([Fault("die_collect", 0, 1)])
        sched, bes, _ = _chaos_fleet(2, 2, plan,
                                     be_cls=ResetFleetScript)
        reqs = _mk_reqs(8, script_len=4, max_new=3)
        sched.serve(reqs)
        assert sched.health == [DRAINED, HEALTHY]
        _check_terminal(sched, reqs)
        assert all(o.status == "delivered"
                   for o in sched.outcomes.values())

    def test_die_collect_with_next_wave_in_flight(self):
        """Replica 0 dies collecting its first wave while its second is
        in flight: both waves and its queue move to replica 1, and every
        request is delivered exactly once."""
        plan = FaultPlan([Fault("die_collect", 0, 1)])
        events = []
        inner = [FleetOneShot(i, events) for i in range(2)]
        bes = [ChaosBackend(b, plan, replica=i)
               for i, b in enumerate(inner)]
        sched = FleetScheduler(bes, batch=2)
        reqs = [Req(i, [], 1) for i in range(12)]
        sched.serve(reqs)
        assert sched.health == [DRAINED, HEALTHY]
        assert [e["fault"] for e in sched.fault_events] == ["ReplicaDead"]
        # the fault hit wave 0 with wave 1 dispatched behind it
        assert inner[0].waves == [[0, 1], [4, 5]]
        assert inner[0].delivered == []
        assert sorted(inner[1].delivered) == list(range(12))
        assert all(o.status == "delivered" and o.replica == 1
                   for o in sched.outcomes.values())
        assert [r.out for r in reqs] == [[10 * i] for i in range(12)]

    def test_partial_stream_lost_without_reset(self):
        """A request whose delivery already started can only be re-served
        if the backend can reset it; FleetScript (no reset) refuses with
        partial_stream_lost instead of emitting a duplicate stream."""
        plan = FaultPlan([Fault("die_dispatch", 0, 2)])
        sched, bes, _ = _chaos_fleet(2, 1, plan)  # no reset hook
        long = Req(0, [7] * 6, 6)
        short = Req(1, [8] * 2, 2)
        sched.serve([long, short])
        assert short.outcome.status == "delivered"
        assert long.outcome.status == "refused"
        assert long.outcome.reason == "partial_stream_lost"
        # the partial stream was not extended after the refusal
        assert 0 < len(long.out) < 6

    def test_all_replicas_dead_refuses_everything(self):
        plan = FaultPlan([Fault("die_dispatch", 0, 0)])
        sched, bes, _ = _chaos_fleet(1, 2, plan)
        reqs = _mk_reqs(4)
        stats = sched.serve(reqs)
        assert stats == []
        assert sched.health == [DRAINED]
        _check_terminal(sched, reqs)
        assert all(o.status == "refused" and
                   o.reason == "no_healthy_replicas"
                   for o in sched.outcomes.values())

    def test_dead_fleet_refuses_next_serve_at_admission(self):
        plan = FaultPlan([Fault("die_dispatch", 0, 0)])
        sched, bes, _ = _chaos_fleet(1, 2, plan)
        sched.serve(_mk_reqs(2))
        later = _mk_reqs(2)
        assert sched.serve(later) == []
        assert all(r.outcome.reason == "no_healthy_replicas"
                   for r in later)


class TestHealthStates:
    def test_transient_marks_suspect_then_quarantines(self):
        """One transient -> suspect (replica keeps serving); reaching
        suspect_limit quarantines and drains it."""
        plan = FaultPlan([Fault("transient", 0, 1)])
        sched, bes, _ = _chaos_fleet(2, 2, plan,
                                     be_cls=ResetFleetScript)
        reqs = _mk_reqs(8, max_new=3)
        sched.serve(reqs)
        assert sched.health[0] == SUSPECT
        assert sched.fault_counts[0] == 1
        _check_terminal(sched, reqs)
        assert all(o.status == "delivered"
                   for o in sched.outcomes.values())

        plan2 = FaultPlan([Fault("transient", 0, 1),
                           Fault("transient", 0, 2)])
        sched2, _, _ = _chaos_fleet(2, 2, plan2, be_cls=ResetFleetScript)
        reqs2 = _mk_reqs(8, max_new=3)
        sched2.serve(reqs2)
        assert sched2.health[0] == DRAINED   # quarantined, then drained
        _check_terminal(sched2, reqs2)
        assert all(o.status == "delivered"
                   for o in sched2.outcomes.values())

    def test_start_fail_quarantines_and_replaces(self):
        """A compile failure admitting a run is non-transient: quarantine;
        the admission wave is re-placed on the survivor."""
        plan = FaultPlan([Fault("start_fail", 0, 0)])
        sched, bes, _ = _chaos_fleet(2, 2, plan)
        reqs = _mk_reqs(4)
        sched.serve(reqs)
        assert sched.health == [DRAINED, HEALTHY]
        assert [e["fault"] for e in sched.fault_events] == ["CompileFault"]
        _check_terminal(sched, reqs)
        assert all(o.status == "delivered"
                   for o in sched.outcomes.values())

    def test_stall_lets_survivors_steal(self):
        """A stalled wave produces nothing for N ticks; the other replica
        keeps retiring and steals the stalled replica's queue — then the
        stalled wave completes normally."""
        plan = FaultPlan([Fault("stall", 0, 1, ticks=4)])
        sched, bes, events = _chaos_fleet(2, 1, plan)
        reqs = [Req(i, [i + 10] * 2, 2) for i in range(6)]
        sched.serve(reqs)
        _check_terminal(sched, reqs)
        assert all(o.status == "delivered"
                   for o in sched.outcomes.values())
        assert sched.health == [HEALTHY, HEALTHY]  # a stall is not a fault
        assert sched.steals >= 1
        assert ("stall" in [k for _, k in bes[0].injected])

    def test_stalled_wave_running_ahead_is_served_later(self):
        """A one-shot replica running ahead: a stalled wave emits nothing,
        its requests go back to the head of the queue and ride a later
        wave — each delivered exactly once, and a stall is not a fault."""
        plan = FaultPlan([Fault("stall", 0, 1, ticks=2)])
        inner = OneShotBackend()
        sched = FleetScheduler([ChaosBackend(inner, plan, replica=0)],
                               batch=2)
        reqs = [Req(i, [], 1) for i in range(7)]
        sched.serve(reqs)
        assert [k for _, k in sched.backends[0].injected] == ["stall"] * 2
        assert sched.health == [HEALTHY] and sched.fault_events == []
        # the stalled waves [0, 1] and [2, 3] never reached the backend
        assert inner.waves == [[4, 5], [0, 1], [2, 3], [6, None]]
        assert sorted(inner.delivered) == list(range(7))
        assert [r.out for r in reqs] == [[10 * i] for i in range(7)]
        assert all(o.status == "delivered" for o in sched.outcomes.values())


class TestBudgets:
    def test_retry_budget_exhausted(self):
        """Endless transients on the only replica burn each displaced
        request's attempt budget down to a structured refusal — never an
        exception, never a hang."""
        plan = FaultPlan([Fault("transient", 0, w) for w in range(30)])
        sched, bes, _ = _chaos_fleet(
            1, 2, plan,
            sched_kw={"max_attempts": 2, "suspect_limit": 100})
        reqs = _mk_reqs(4)
        sched.serve(reqs)
        _check_terminal(sched, reqs)
        assert all(o.status == "refused" and
                   o.reason == "retry_budget_exhausted"
                   for o in sched.outcomes.values())
        assert all(o.attempts == 3 for o in sched.outcomes.values())

    def test_deadline_refuses_queued_not_inflight(self):
        """deadline_waves counts fleet ticks: a request still queued past
        the budget is refused; the in-flight one always completes."""
        sched, bes, _ = _chaos_fleet(
            1, 1, FaultPlan(), sched_kw={"deadline_waves": 3})
        slow = Req(0, [5] * 10, 10)
        waiting = Req(1, [6] * 2, 2)
        sched.serve([slow, waiting])
        assert slow.outcome.status == "delivered"
        assert waiting.outcome.status == "refused"
        assert waiting.outcome.reason == "deadline_exceeded"
        assert waiting.outcome.wave == 3

    def test_per_request_deadline_overrides_default(self):
        sched, bes, _ = _chaos_fleet(
            1, 1, FaultPlan(), sched_kw={"deadline_waves": 100})
        slow = Req(0, [5] * 10, 10)
        waiting = Req(1, [6] * 2, 2)
        waiting.deadline_waves = 2
        sched.serve([slow, waiting])
        assert waiting.outcome.reason == "deadline_exceeded"
        assert slow.outcome.status == "delivered"

    def test_fleet_max_queue_sheds(self):
        sched, bes, _ = _chaos_fleet(
            2, 2, FaultPlan(), sched_kw={"max_queue": 3})
        reqs = _mk_reqs(5)
        sched.serve(reqs)
        _check_terminal(sched, reqs)
        statuses = [r.outcome.status for r in reqs]
        assert statuses == ["delivered"] * 3 + ["refused"] * 2
        assert all(r.outcome.reason == "queue_full" for r in reqs[3:])


class TestReplay:
    def test_chaos_run_replays_identically(self):
        """Same plan + same queue on a fresh fleet: identical outcome
        trajectory (status/reason/replica/attempts/wave per request),
        fault events, health, waves and steals."""
        plan = FaultPlan.random(3, replicas=3, horizon=8, rate=0.3)

        def run():
            sched, _, _ = _chaos_fleet(
                3, 2, plan, be_cls=ResetFleetScript,
                sched_kw={"deadline_waves": 12, "max_attempts": 2})
            reqs = _mk_reqs(10, script_len=5, max_new=4)
            sched.serve(reqs)
            trace = {rid: dataclasses.astuple(o)
                     for rid, o in sched.outcomes.items()}
            return (trace, sched.fault_events, sched.health, sched.waves,
                    sched.steals, [r.out for r in reqs])
        assert run() == run()


class TestProperties:
    @settings(max_examples=25, deadline=None)
    @given(seed=st.integers(0, 10_000), replicas=st.integers(1, 4),
           rate=st.floats(0.0, 0.5), nreq=st.integers(1, 12))
    def test_every_admitted_request_gets_one_terminal_outcome(
            self, seed, replicas, rate, nreq):
        """The tentpole invariant under randomized chaos: every admitted
        request ends in exactly one terminal outcome; delivered streams
        are exact (no loss, no duplication); the serve always returns."""
        plan = FaultPlan.random(seed, replicas=replicas, horizon=12,
                                rate=rate)
        sched, bes, _ = _chaos_fleet(
            replicas, 2, plan, be_cls=ResetFleetScript,
            sched_kw={"deadline_waves": 40, "max_attempts": 3})
        reqs = _mk_reqs(nreq, script_len=4, max_new=3)
        sched.serve(reqs)
        _check_terminal(sched, reqs)
        # drained replicas stay drained; healthy ones have no fault count
        for h, c in zip(sched.health, sched.fault_counts):
            if h == HEALTHY:
                assert c == 0

    @settings(max_examples=15, deadline=None)
    @given(seed=st.integers(0, 10_000))
    def test_work_stealing_never_duplicates(self, seed):
        """Queues move between ladders and runs under chaos, but a request
        is only ever in one place: delivered exactly once with exactly its
        scripted stream."""
        plan = FaultPlan.random(seed, replicas=3, horizon=10, rate=0.25)
        sched, bes, _ = _chaos_fleet(
            3, 1, plan, be_cls=ResetFleetScript,
            sched_kw={"max_attempts": 4})
        reqs = _mk_reqs(9, script_len=3, max_new=3)
        sched.serve(reqs)
        _check_terminal(sched, reqs)
        delivered = [r for r in reqs
                     if sched.outcomes[r.rid].status == "delivered"]
        for r in delivered:
            assert r.out == r.script[:3]


# -- real-model acceptance gate ---------------------------------------------

from repro.configs import get_config            # noqa: E402
from repro.launch.serve import CNNServer, ImageRequest  # noqa: E402


@pytest.fixture(scope="module")
def cnn():
    """One shared CNNBackend (+ its jit cache) for the whole sweep: the
    backend is stateless across runs, so every chaos fleet can wrap the
    same instance and the 20+ serves below stay fast."""
    cfg = get_config("vscnn-vgg16").reduce()
    srv = CNNServer(cfg, batch=2, seed=0)
    return cfg, srv.backend


def _images(cfg, n):
    rng = np.random.default_rng(0)
    s = cfg.image_size
    return [ImageRequest(
                rid=i,
                image=rng.standard_normal((s, s, 3)).astype(np.float32))
            for i in range(n)]


def _cnn_fleet(be, plan, *, replicas=3, batch=2):
    bes = [ChaosBackend(be, plan, replica=i) for i in range(replicas)]
    return FleetScheduler(bes, batch=batch)


class TestCNNFaultSweep:
    def test_death_at_every_position_bit_identical(self, cnn):
        """The acceptance criterion: replica death (and NaN corruption)
        injected at every (replica, wave, kind) schedule position of a
        3-replica fleet still delivers every request with logits
        bit-identical to the fault-free fleet."""
        cfg, be = cnn
        ref_sched = _cnn_fleet(be, FaultPlan())
        ref = _images(cfg, 8)
        ref_sched.serve(ref)
        assert all(o.status == "delivered"
                   for o in ref_sched.outcomes.values())
        ref_logits = [r.logits.tobytes() for r in ref]
        for kind in ("die_dispatch", "die_collect", "nan"):
            for replica in range(3):
                for wave in range(3):
                    plan = FaultPlan([Fault(kind, replica, wave)])
                    sched = _cnn_fleet(be, plan)
                    reqs = _images(cfg, 8)
                    sched.serve(reqs)
                    pos = f"{kind}@r{replica}w{wave}"
                    assert all(o.status == "delivered" for o in
                               sched.outcomes.values()), pos
                    got = [r.logits.tobytes() for r in reqs]
                    assert got == ref_logits, pos
                    fired = [k for b in sched.backends
                             for _, k in b.injected]
                    if fired:  # the fault actually hit the schedule
                        assert sched.fault_events, pos
                        assert sched.health[replica] == DRAINED, pos

    def test_nan_guard_quarantines_producer(self, cnn):
        """The output guard catches the corrupted wave before any
        delivery: the producing replica is quarantined and the wave's
        requests are re-served elsewhere with finite logits."""
        cfg, be = cnn
        plan = FaultPlan([Fault("nan", 0, 1)])
        sched = _cnn_fleet(be, plan)
        reqs = _images(cfg, 8)
        sched.serve(reqs)
        assert all(o.status == "delivered"
                   for o in sched.outcomes.values())
        assert all(np.isfinite(r.logits).all() for r in reqs)
        assert sched.health[0] == DRAINED
        assert [e["fault"] for e in sched.fault_events] == \
            ["NonFiniteOutput"]

    def test_one_replica_refuses_non_finite_output(self, cnn):
        """A one-replica server runs the same guard: non-finite logits
        quarantine its only replica, and the wave is refused instead of
        delivered."""
        cfg, _ = cnn
        plan = FaultPlan([Fault("nan", 0, 1)])
        srv = CNNServer(cfg, batch=2, seed=0, fault_plan=plan,
                        validate=False)
        reqs = _images(cfg, 4)
        srv.serve(reqs)
        assert srv.scheduler.health == [DRAINED]
        assert [e["fault"] for e in srv.scheduler.fault_events] == \
            ["NonFiniteOutput"]
        assert all(o.status == "refused" and o.reason == "no_healthy_replicas"
                   for o in srv.outcomes.values())
        assert all(r.logits is None for r in reqs)

    def test_cnn_chaos_replay_identical(self, cnn):
        """Same seeded plan, fresh fleets: identical health, fault
        events, waves, steals, outcomes and logits bytes."""
        cfg, be = cnn
        plan = FaultPlan.random(11, replicas=3, horizon=6, rate=0.3)

        def run():
            sched = _cnn_fleet(be, plan)
            reqs = _images(cfg, 8)
            sched.serve(reqs)
            trace = {rid: dataclasses.astuple(o)
                     for rid, o in sched.outcomes.items()}
            return (trace, sched.fault_events, sched.health, sched.waves,
                    sched.steals,
                    [r.logits.tobytes() if r.logits is not None else None
                     for r in reqs])
        assert run() == run()

    def test_cnnserver_chaos_integration(self, cnn):
        """`CNNServer(fault_plan=...)` wires the chaos fleet end to end:
        structured outcomes on the server, no exception, health exposed."""
        cfg, _ = cnn
        plan = FaultPlan([Fault("die_dispatch", 0, 1)])
        srv = CNNServer(cfg, batch=2, seed=0, replicas=2,
                        fault_plan=plan, validate=False)
        reqs = _images(cfg, 6)
        srv.serve(reqs)
        assert all(o.status == "delivered"
                   for o in srv.outcomes.values())
        assert srv.scheduler.health == [DRAINED, HEALTHY]
