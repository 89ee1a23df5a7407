"""Fault-tolerant serving loop: lifecycle, deadline shedding, retry /
backoff, circuit-breaker degradation, drain-mid-storm, and the chaos
suite proving the drop-free invariant — every submitted rid reaches
exactly one terminal state (DONE | SHED | FAILED) and the ledger's
served+shed+failed reconciliation matches the loop's counters, under
every seeded fault schedule, including clock skew.

Everything deterministic runs on a VirtualClock (backoff waits and
injected delays are free); the async-overlap and functional-
degradation tests use real time with a reduced-width compute stack.
"""

import asyncio
import functools
import importlib.util
import math
import random
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from _hypothesis_compat import given, settings, st
from repro.models.cnn import init_vgg, vgg_graph
from repro.models.graph import graph_logits
from repro.obs import Tracer
from repro.serve import (CircuitBreaker, FaultEvent, FaultPlan,
                         ImageServer, InjectedFault, RequestState,
                         ServingLoop, VirtualClock)

REPO = Path(__file__).resolve().parent.parent


def _load(path: Path):
    spec = importlib.util.spec_from_file_location(path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.lru_cache(maxsize=1)
def _tiny_params():
    return init_vgg(jax.random.PRNGKey(0), n_classes=4, width_mult=0.05)


def _account_server(clock, **kw):
    kw.setdefault("wait_budget", 0.01)
    return ImageServer(_tiny_params(), 8, 8, compute=False, clock=clock,
                       **kw)


def _assert_reconciled(loop):
    """The drop-free invariant: every rid terminal exactly once, and
    the ledger's terminal-state rows match the loop's counters."""
    assert loop.all_terminal()
    c = loop.counters
    assert c["done"] + c["shed"] + c["failed"] == c["submitted"]
    states = [t.state for t in loop.requests.values()]
    assert len(states) == c["submitted"]
    assert sum(s is RequestState.DONE for s in states) == c["done"]
    assert sum(s is RequestState.SHED for s in states) == c["shed"]
    assert sum(s is RequestState.FAILED for s in states) == c["failed"]
    led = loop.server.ledger
    assert led.submitted_requests == c["submitted"]
    assert led.shed_requests == c["shed"]
    assert led.failed_requests == c["failed"]
    s = led.summary()
    assert s["served_requests"] == c["done"]
    assert s["goodput"] == pytest.approx(
        c["done"] / max(c["submitted"], 1))
    # no negative latency may ever be charged, skew or not
    for ch in led.charges:
        assert ch.latency_s is None or ch.latency_s >= 0.0


# --------------------------------------------------------------------------
# lifecycle basics
# --------------------------------------------------------------------------

def test_full_bucket_lifecycle_all_done():
    clock = VirtualClock()
    loop = ServingLoop(_account_server(clock), deadline_s=1.0)
    rids = [loop.submit(n_images=n) for n in (4, 2, 1, 1)]
    for rid in rids:
        assert loop.state_of(rid) is RequestState.PENDING
    results = loop.pump()                 # 4+2+1+1 == full 8-bucket
    assert sorted(r.rid for r in results) == sorted(rids)
    assert all(loop.state_of(r) is RequestState.DONE for r in rids)
    assert all(loop.requests[r].attempts == 1 for r in rids)
    _assert_reconciled(loop)
    assert loop.counters["done"] == 4
    assert loop.server.ledger.summary()["goodput"] == 1.0


def test_direct_server_submissions_are_adopted():
    """Requests enqueued on the server behind the loop's back still
    get a lifecycle record and terminate."""
    clock = VirtualClock()
    srv = _account_server(clock)
    loop = ServingLoop(srv, deadline_s=1.0)
    rid = srv.submit(n_images=8)          # bypasses loop.submit
    loop.pump()
    assert loop.state_of(rid) is RequestState.DONE
    assert loop.all_terminal()


# --------------------------------------------------------------------------
# deadline shedding
# --------------------------------------------------------------------------

def test_admission_sheds_when_projected_wait_exceeds_budget():
    """A storm beyond capacity sheds at admission — a fast negative
    instead of a guaranteed timeout — and every shed rid is terminal
    with a ledger row."""
    clock = VirtualClock()
    loop = ServingLoop(_account_server(clock), deadline_s=0.1,
                       fault_plan=FaultPlan(service_s=0.05),
                       service_estimate_s=0.05, seed=0)
    rids = [loop.submit(n_images=1) for _ in range(24)]
    shed = [r for r in rids if loop.state_of(r) is RequestState.SHED]
    assert shed and len(shed) == loop.counters["shed_admission"]
    for rid in shed:
        assert "projected wait" in loop.requests[rid].shed_reason
    loop.run_sync(tick_s=0.01)
    _assert_reconciled(loop)
    # admission sheds plus any that expired while queued; never all
    assert loop.counters["shed"] >= len(shed)
    assert loop.counters["done"] == 24 - loop.counters["shed"]
    assert 0.0 < loop.server.ledger.summary()["shed_frac"] < 1.0


def test_expired_requests_shed_at_pop_time():
    """A request whose budget lapsed while queued is shed when its
    group pops, never dispatched dead-on-arrival."""
    clock = VirtualClock()
    srv = _account_server(clock, wait_budget=0.3)
    loop = ServingLoop(srv, deadline_s=0.25)
    rid = loop.submit(n_images=3)         # partial bucket: waits
    assert loop.pump() == []
    clock.sleep(0.4)                      # past wait budget AND deadline
    assert loop.pump() == []
    assert loop.state_of(rid) is RequestState.SHED
    assert loop.counters["shed_expired"] == 1
    assert "queued" in loop.requests[rid].shed_reason
    _assert_reconciled(loop)


# --------------------------------------------------------------------------
# retry / backoff and terminal failure
# --------------------------------------------------------------------------

def test_transient_failure_retries_with_backoff_then_succeeds():
    clock = VirtualClock()
    plan = FaultPlan.failures(0)
    loop = ServingLoop(_account_server(clock), deadline_s=10.0,
                       fault_plan=plan, seed=1)
    rids = [loop.submit(n_images=4), loop.submit(n_images=4)]
    assert loop.pump() == []              # attempt 0 injected to fail
    assert loop.counters["dispatch_failures"] == 1
    assert loop.counters["retries"] == 1
    assert loop.stats["retry_backlog"] == 1
    t_fail = clock.now
    loop.run_sync(tick_s=0.01)            # ticks reach the backoff due
    assert clock.now >= t_fail + 0.9 * loop.backoff_base_s
    assert all(loop.state_of(r) is RequestState.DONE for r in rids)
    assert all(loop.requests[r].attempts == 2 for r in rids)
    assert [e.kind for e in plan.triggered] == ["fail"]
    _assert_reconciled(loop)


def test_exhausted_retries_fail_terminally():
    clock = VirtualClock()
    loop = ServingLoop(_account_server(clock), deadline_s=None,
                       max_retries=2,
                       fault_plan=FaultPlan.failures(*range(50)))
    rids = [loop.submit(n_images=8) for _ in range(2)]
    loop.run_sync(tick_s=0.01)
    for rid in rids:
        t = loop.requests[rid]
        assert t.state is RequestState.FAILED
        assert "InjectedFault" in t.error
    assert loop.counters["failed"] == 2
    assert loop.server.ledger.failed_images == 16
    _assert_reconciled(loop)


def test_drain_mid_storm_drops_nothing():
    """Shutdown while the queue holds work and every dispatch keeps
    failing: drain still walks each rid to a terminal state."""
    clock = VirtualClock()
    srv = _account_server(clock, buckets=(1,), wait_budget=10.0)
    loop = ServingLoop(srv, deadline_s=None, max_retries=2,
                       fault_plan=FaultPlan.failures(*range(50)))
    rids = [loop.submit(n_images=1) for _ in range(5)]
    loop.pump()                           # first attempts fail -> retries
    assert not loop.all_terminal()
    assert loop.drain() == []
    assert all(loop.state_of(r) is RequestState.FAILED for r in rids)
    assert loop.counters["dispatch_failures"] == 15   # 3 attempts x 5
    _assert_reconciled(loop)


# --------------------------------------------------------------------------
# circuit breaker: downward ExecTarget ladder (interpret -> lax ->
# account-only, from the server's own target ceiling)
# --------------------------------------------------------------------------

def test_breaker_degrades_down_the_ladder_and_ledger_counts_it():
    srv = ImageServer(_tiny_params(), 8, 8, buckets=(2,),
                      wait_budget=0.0)
    loop = ServingLoop(srv, deadline_s=None,
                       breaker_threshold=1, max_retries=5,
                       fault_plan=FaultPlan.failures(0, 1))
    imgs = jnp.ones((2, 8, 8, 3))
    rid = loop.submit(imgs)
    loop.run_sync(tick_s=0.01)
    assert loop.state_of(rid) is RequestState.DONE
    assert loop.breaker.trips == 2
    assert loop.breaker.mode.name == "account-only"
    assert loop.server.ledger.degraded_dispatches == 1
    _assert_reconciled(loop)


def test_breaker_ladder_is_capped_at_the_servers_own_target():
    """An account-only server has a one-rung ladder: the breaker can
    never degrade (or "recover" upward past the server's ceiling)."""
    clock = VirtualClock()
    loop = ServingLoop(_account_server(clock), deadline_s=None,
                       breaker_threshold=1, max_retries=5,
                       fault_plan=FaultPlan.failures(0, 1))
    assert [t.name for t in loop.breaker.ladder] == ["account-only"]
    rid = loop.submit(n_images=8)
    loop.run_sync(tick_s=0.01)
    assert loop.state_of(rid) is RequestState.DONE
    assert loop.breaker.trips == 0
    assert loop.breaker.mode.name == "account-only"
    assert loop.server.ledger.degraded_dispatches == 0
    _assert_reconciled(loop)


def test_breaker_steps_back_up_after_cooldown():
    br = CircuitBreaker(threshold=2, cooldown_s=1.0)
    assert br.mode.name == "interpret"    # default ladder ceiling
    br.record_failure(0.0)
    assert br.level == 0                  # below threshold
    br.record_failure(0.0)
    assert (br.level, br.mode.name, br.trips) == (1, "lax", 1)
    br.record_success(0.5)                # inside cooldown: stays
    assert br.level == 1
    br.record_success(1.6)                # cooled down: half-open re-probe
    assert (br.level, br.mode.name) == (0, "interpret")


def test_breaker_routes_around_a_poisoned_kernel_path():
    """Functional degradation on a real compute stack: the kernel
    pipeline raises, the breaker falls back to lax, and the served
    logits match the direct lax forward."""
    params = _tiny_params()
    graph = vgg_graph(params)

    def forward(p, imgs, target):
        if target.kernel:
            raise RuntimeError("kernel path poisoned")
        return graph_logits(graph, p, imgs, target=target)

    srv = ImageServer(params, 8, 8, graph=graph, forward=forward,
                      buckets=(2,), wait_budget=0.0)
    loop = ServingLoop(srv, deadline_s=None, breaker_threshold=1,
                       max_retries=3, backoff_base_s=0.01)
    imgs = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 8, 3))
    rid = loop.submit(imgs)
    (res,) = loop.run_sync(tick_s=0.005)
    assert loop.state_of(rid) is RequestState.DONE
    assert loop.breaker.mode.name == "lax"
    assert jnp.allclose(res.logits,
                        graph_logits(graph, params, imgs,
                                     target="lax"), atol=1e-5)
    assert srv.ledger.degraded_dispatches == 1


# --------------------------------------------------------------------------
# clock skew
# --------------------------------------------------------------------------

def test_clock_skew_never_charges_negative_latency():
    clock = VirtualClock(start=10.0)
    plan = FaultPlan([FaultEvent(at=0, kind="skew", value=-5.0)],
                     service_s=0.01)
    loop = ServingLoop(_account_server(clock), deadline_s=None,
                       fault_plan=plan)
    loop.submit(n_images=8)
    (res,) = loop.run_sync(tick_s=0.01)
    assert clock.now < 10.0               # the skew really fired
    assert res.latency_s >= 0.0
    assert res.charge.latency_s >= 0.0
    _assert_reconciled(loop)


# --------------------------------------------------------------------------
# chaos suite: drop-free invariant under seeded random schedules
# --------------------------------------------------------------------------

def _run_chaos(seed: int) -> ServingLoop:
    """One seeded episode: random arrivals + sizes + pump cadence,
    FaultPlan.random(seed) faults (fails, delays, skews), then run to
    quiescence.  Bit-identical per seed by construction."""
    rng = random.Random(seed)
    clock = VirtualClock()
    loop = ServingLoop(
        _account_server(clock, wait_budget=0.05),
        deadline_s=rng.choice([0.15, 0.5, None]),
        max_retries=rng.randint(1, 3),
        fault_plan=FaultPlan.random(seed, service_s=0.02),
        service_estimate_s=rng.choice([0.0, 0.02]),
        seed=seed)
    for _ in range(rng.randint(5, 15)):
        clock.sleep(rng.uniform(0.0, 0.08))
        loop.submit(n_images=rng.randint(1, 4))
        if rng.random() < 0.5:
            loop.pump()
    loop.run_sync(tick_s=0.01)
    _assert_reconciled(loop)
    s = loop.server.ledger.summary()
    if s.get("measured_latencies"):
        assert s["p50_latency_s"] >= 0.0
        assert s["p99_latency_s"] >= s["p50_latency_s"]
    return loop


def test_chaos_known_seeds_cover_all_fault_kinds():
    """A few fixed seeds chosen to exercise failure, delay and skew
    events together (FaultPlan.random logs what fired)."""
    kinds = set()
    for seed in (0, 3, 7, 11, 23):
        loop = _run_chaos(seed)
        kinds |= {e.kind for e in loop.fault_plan.triggered}
    assert kinds >= {"fail", "delay"}


@settings(max_examples=12, deadline=None)
@given(st.integers(min_value=0, max_value=99_999))
def test_chaos_drop_free_invariant_property(seed):
    _run_chaos(seed)


def test_chaos_replay_is_deterministic():
    a, b = _run_chaos(42), _run_chaos(42)
    assert a.counters == b.counters
    assert ([t.state for t in a.requests.values()]
            == [t.state for t in b.requests.values()])
    assert ([(e.at, e.kind) for e in a.fault_plan.triggered]
            == [(e.at, e.kind) for e in b.fault_plan.triggered])


# --------------------------------------------------------------------------
# async driver: in-flight overlap
# --------------------------------------------------------------------------

def test_async_driver_overlaps_up_to_max_inflight():
    srv = ImageServer(_tiny_params(), 8, 8, compute=False,
                      buckets=(1,), wait_budget=0.0)
    loop = ServingLoop(srv, deadline_s=None, max_inflight=2,
                       fault_plan=FaultPlan(service_s=0.05))
    for _ in range(4):
        loop.submit(n_images=1)
    results = asyncio.run(loop.run_async())
    assert len(results) == 4
    assert loop.counters["peak_inflight"] == 2
    _assert_reconciled(loop)


@pytest.mark.parametrize("runner", ["run_sync", "run_async"])
def test_execute_runs_off_lock_and_complete_under_it(runner):
    """The host copy of a dispatch's logits is taken in ``_execute``,
    with the loop's lock released, so admission overlaps it; the
    completion bookkeeping in ``_complete`` runs with the lock held by
    the completing thread, so a request's terminal state and its
    ledger row change together."""
    srv = ImageServer(_tiny_params(), 8, 8, target="lax",
                      buckets=(1, 2, 4), wait_budget=0.0)
    loop = ServingLoop(srv, deadline_s=None, max_inflight=2)
    held = {"_execute": [], "_complete": []}

    def watched(name):
        inner = getattr(srv, name)

        def call(*a, **kw):
            held[name].append(loop._lock._is_owned())
            return inner(*a, **kw)
        return call

    for name in held:
        setattr(srv, name, watched(name))
    images = jnp.ones((4, 8, 8, 3))
    for n in (1, 2, 1, 4):
        loop.submit(images[:n])
    if runner == "run_sync":
        results = loop.run_sync(tick_s=0.0)
    else:
        results = asyncio.run(loop.run_async())
    assert sum(r.logits.shape[0] for r in results) == 8
    assert held["_execute"] and not any(held["_execute"])
    assert len(held["_complete"]) == len(held["_execute"])
    assert all(held["_complete"])
    assert srv.stats["host_copies"] == srv.stats["dispatches"]
    _assert_reconciled(loop)


# --------------------------------------------------------------------------
# fault-injection plumbing
# --------------------------------------------------------------------------

def test_virtual_clock_sleep_clamps_and_jump_skews():
    c = VirtualClock(start=1.0)
    c.sleep(0.5)
    c.sleep(-3.0)                         # sleeps never rewind
    assert c() == 1.5
    c.jump(-0.7)                          # skews may
    assert c() == pytest.approx(0.8)


def test_fault_event_rejects_unknown_kind():
    with pytest.raises(ValueError):
        FaultEvent(at=0, kind="explode")


def test_fault_plan_fail_is_fail_fast_and_logged():
    plan = FaultPlan.failures(1, service_s=0.02)
    assert plan.before_dispatch(0, 8) == pytest.approx(0.02)
    with pytest.raises(InjectedFault):
        plan.before_dispatch(1, 8)
    assert [e.at for e in plan.triggered] == [1]


def test_fault_plan_bucket_restriction():
    plan = FaultPlan([FaultEvent(at=0, kind="fail", bucket=4)])
    assert plan.before_dispatch(0, 8) == 0.0     # other bucket: no-op
    with pytest.raises(InjectedFault):
        plan.before_dispatch(0, 4)


def test_fault_plan_random_is_seed_deterministic():
    a, b = FaultPlan.random(9), FaultPlan.random(9)
    assert a.events == b.events
    assert FaultPlan.random(10).events != a.events


def test_fault_plan_parse_spec_and_random():
    plan = FaultPlan.parse("fail@1,delay@3:0.05,skew@6:-0.2,service:0.01")
    assert [(e.at, e.kind, e.value) for e in plan.events] == [
        (1, "fail", 0.0), (3, "delay", 0.05), (6, "skew", -0.2)]
    assert plan.service_s == pytest.approx(0.01)
    assert FaultPlan.parse("random:7").events \
        == FaultPlan.random(7).events
    with pytest.raises(ValueError):
        FaultPlan.parse("fail")           # missing @AT
    with pytest.raises(ValueError):
        FaultPlan.parse("explode@1")      # unknown kind


# --------------------------------------------------------------------------
# acceptance: bursty trace through the full-scale loop
# --------------------------------------------------------------------------

def test_bursty_trace_sheds_bounded_and_stays_within_bound():
    """The benchmark's bursty VGG16/224 trace as an acceptance test:
    the storm's tail sheds (bounded by the deadline policy, not a
    collapse), served requests stay within 1.25x the Eq. (15) bound,
    and p99 latency respects the budget."""
    sb = _load(REPO / "benchmarks" / "serve_bench.py")
    rows = {name: val for name, _, val in sb.bench_serve_loop_bursty()}
    shed = rows["serve_loop/vgg16_bursty/serve_shed_frac"]
    assert 0.0 < shed <= 0.35             # sheds, but only the overrun
    assert rows["serve_loop/vgg16_bursty/serve_goodput_rps"] > 0
    assert rows["serve_loop/vgg16_bursty/serve_p99_x_budget"] <= 1.0
    assert rows["serve_loop/vgg16_bursty/vs_bound_x"] <= 1.25
    assert all(math.isfinite(v) for v in rows.values())


# --------------------------------------------------------------------------
# CLI smoke: --deadline / --fault-plan on both drivers
# --------------------------------------------------------------------------

def test_example_serve_images_fault_loop_smoke(monkeypatch, capsys):
    mod = _load(REPO / "examples" / "serve_images.py")
    monkeypatch.setattr(sys, "argv",
                        ["serve_images.py", "--requests", "3",
                         "--image", "8", "--width-mult", "0.05",
                         "--deadline", "5.0", "--fault-plan",
                         "fail@0"])
    mod.main()
    out = capsys.readouterr().out
    assert "loop:" in out and "health:" in out
    assert "'retries': 1" in out          # the injected failure retried


def test_launch_serve_images_fault_loop_smoke(monkeypatch, capsys):
    from repro.launch import serve_images
    monkeypatch.setattr(sys, "argv",
                        ["serve_images", "--account-only",
                         "--width-mult", "1.0", "--image", "224",
                         "--requests", "6", "--deadline", "0.25",
                         "--fault-plan", "fail@1,service:0.01"])
    serve_images.main()
    out = capsys.readouterr().out
    assert "loop:" in out and "health:" in out


def test_dispatch_stamp_marks_the_first_attempt():
    """dispatched_at is the loop clock at a request's first dispatch
    attempt: a retry keeps it, and a request shed before any dispatch
    has none."""
    clock = VirtualClock()
    tracer = Tracer(clock=clock)
    loop = ServingLoop(_account_server(clock, tracer=tracer),
                       deadline_s=0.12,
                       fault_plan=FaultPlan.failures(0, service_s=0.05),
                       service_estimate_s=0.05, seed=0)
    rids = [loop.submit(n_images=8) for _ in range(5)]
    for rid in rids:
        assert loop.requests[rid].dispatched_at is None
    clock.sleep(0.01)
    loop.run_sync(tick_s=0.01)
    _assert_reconciled(loop)
    reqs = [loop.requests[r] for r in rids]
    shed = [t for t in reqs if t.state is RequestState.SHED]
    done = [t for t in reqs if t.state is RequestState.DONE]
    assert shed and done and len(shed) + len(done) == len(reqs)
    assert all(t.dispatched_at is None for t in shed)
    for t in done:
        assert t.arrival <= t.dispatched_at <= t.terminal_at
        first = min(s.t0 for s in tracer.find(name="dispatch.attempt")
                    if str(t.rid) in s.attrs["rids"].split(","))
        assert t.dispatched_at == first
    (retried,) = [t for t in done if t.attempts == 2]
    assert loop.counters["retries"] == 1
    # the retry's own attempt started after the backoff, yet the stamp
    # still reads the first attempt's start
    attempts = sorted(s.t0 for s in tracer.find(name="dispatch.attempt")
                      if str(retried.rid) in s.attrs["rids"].split(","))
    assert len(attempts) == 2
    assert retried.dispatched_at == attempts[0] < attempts[1]
