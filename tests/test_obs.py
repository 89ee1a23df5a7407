"""Observability layer: span-tree tracer, metrics registry, trace
export, and the instrumentation threaded through planning, kernels,
graphs and serving.

The load-bearing guarantees pinned here:

  * span-tree integrity under chaos — for every seeded fault schedule,
    every submitted rid owns exactly one finished ``request`` span and
    exactly one ``request.terminal`` event whose state matches the
    loop's drop-free reconciliation (DONE | SHED | FAILED);
  * deterministic export — the same chaos seed replayed on a fresh
    server under a ``VirtualClock``-driven tracer exports byte-
    identical Perfetto JSON and JSONL files;
  * zero-cost-when-off — the disabled (NULL_TRACER) path's measured
    per-site cost times the sites a real run hits stays under 2% of
    the serve smoke's wall time (analytic, not a flaky A/B);
  * bytes-vs-seconds attribution — kernel spans carry both the
    accounted ``traffic_bytes`` and synced ``us``, i.e. an achieved-
    GB/s sample per layer.
"""

import json
import random
import sys
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.models.cnn import init_vgg, vgg_graph
from repro.models.graph import graph_forward
from repro.obs import (MetricsRegistry, NULL_TRACER, Tracer,
                       active_tracer, chrome_trace, events_jsonl,
                       timed_call, write_trace)
from repro.obs.tracer import NULL_SPAN
from repro.serve import (FaultPlan, ImageServer, RequestState,
                         ServingLoop, VirtualClock)

from test_serve_loop import _load, _tiny_params

REPO = Path(__file__).resolve().parent.parent


# --------------------------------------------------------------------------
# tracer core
# --------------------------------------------------------------------------

def test_span_nesting_and_attrs():
    ticks = iter(range(100))
    tr = Tracer(clock=lambda: float(next(ticks)))
    with tr.span("outer", rid=7) as outer:
        with tr.span("inner", layer="conv1") as inner:
            inner.set(traffic_bytes=123)
        tr.event("mark", bucket=4)
    outer_r, inner_r, ev = tr.records
    assert outer_r is outer and outer_r.parent is None
    assert inner_r.parent == outer_r.sid
    assert ev.parent == outer_r.sid and ev.kind == "instant"
    assert inner_r.attrs == {"layer": "conv1", "traffic_bytes": 123}
    # injected clock: deterministic interval arithmetic
    assert (outer_r.t0, inner_r.t0, inner_r.t1, ev.t0) == (0.0, 1.0,
                                                           2.0, 3.0)
    assert outer_r.dur == outer_r.t1 - 0.0 and outer_r.finished
    assert ev.dur == 0.0


def test_span_decorator_and_error_capture():
    tr = Tracer()

    @tr.span("work", kindof="decorated")
    def work(x):
        return x + 1

    assert work(1) == 2 and work(2) == 3
    assert len(tr.find(name="work", kindof="decorated")) == 2
    with pytest.raises(ValueError):
        with tr.span("boom"):
            raise ValueError("no")
    (sp,) = tr.find(name="boom")
    assert sp.finished and "no" in sp.attrs["error"]


def test_detached_begin_end_crosses_threads():
    tr = Tracer()
    sp = tr.begin("request", rid=1)
    t = threading.Thread(target=lambda: tr.end(sp, state="done"))
    t.start()
    t.join()
    assert sp.finished and sp.attrs["state"] == "done"
    assert sp.tid == "MainThread"      # track of the beginning thread
    # end() is a no-op on the null span (shed-before-begin paths)
    assert tr.end(NULL_SPAN, state="x") is NULL_SPAN


def test_tracer_is_thread_safe_and_sids_unique():
    tr = Tracer()

    def pump(k):
        for i in range(200):
            with tr.span("t", worker=k, i=i):
                pass

    threads = [threading.Thread(target=pump, args=(k,))
               for k in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    recs = tr.records
    assert len(recs) == 1600 and tr.dropped == 0
    assert len({s.sid for s in recs}) == 1600
    assert all(s.finished for s in recs)


def test_max_records_drops_and_counts():
    tr = Tracer(max_records=5)
    for i in range(9):
        tr.event("e", i=i)
    assert len(tr.records) == 5 and tr.dropped == 4
    tr.clear()
    assert tr.records == [] and tr.dropped == 0


def test_tree_builds_the_span_forest():
    tr = Tracer()
    with tr.span("a"):
        with tr.span("b"):
            tr.event("c")
    with tr.span("d"):
        pass
    roots = tr.tree()
    assert [r["span"].name for r in roots] == ["a", "d"]
    (b,) = roots[0]["children"]
    assert b["span"].name == "b"
    assert [c["span"].name for c in b["children"]] == ["c"]


def test_null_tracer_is_inert_and_shared():
    assert NULL_TRACER.span("x", rid=1) is NULL_SPAN
    assert NULL_TRACER.event("x") is NULL_SPAN
    assert NULL_TRACER.begin("x") is NULL_SPAN
    assert not NULL_SPAN and NULL_SPAN.set(a=1) is NULL_SPAN
    assert NULL_SPAN.attrs == {}
    with NULL_SPAN as sp:
        assert sp is NULL_SPAN

    def f(x):
        return x

    assert NULL_SPAN(f) is f           # decorator form: identity
    assert NULL_TRACER.records == [] and not NULL_TRACER.active
    # a disabled real tracer degrades to the same constants
    off = Tracer(enabled=False)
    assert off.span("x") is NULL_SPAN and off.records == []


@pytest.fixture
def annotations(monkeypatch):
    """Names of the profiler annotations entered while the test runs."""
    import jax.profiler

    entered = []

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, name, **kw):
            super().__init__(name, **kw)
            self.counted = name

        def __enter__(self):
            entered.append(self.counted)
            return super().__enter__()

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    return entered


def test_stacked_spans_hold_a_profiler_annotation(annotations):
    tr = Tracer()
    with tr.span("outer"):
        with tr.span("inner"):
            pass
    tr.span("deco")(lambda: None)()
    tr.event("instant")
    tr.end(tr.begin("detached"))
    # stacked spans only: detached spans and events may cross threads
    assert annotations == ["outer", "inner", "deco"]
    with pytest.raises(ValueError):
        with tr.span("failing"):
            raise ValueError("x")
    assert annotations[-1] == "failing"
    assert tr.find(name="failing")[0].finished


def test_null_tracer_creates_no_annotation(annotations):
    with NULL_TRACER.span("x"):
        pass
    NULL_TRACER.span("y")(lambda: None)()
    with Tracer(enabled=False).span("z"):
        pass
    assert annotations == []


def test_activate_scopes_the_ambient_tracer():
    assert active_tracer() is NULL_TRACER
    tr = Tracer()
    with tr.activate() as got:
        assert got is tr and active_tracer() is tr
        inner = Tracer()
        with inner.activate():
            assert active_tracer() is inner
        assert active_tracer() is tr
    assert active_tracer() is NULL_TRACER


def test_timed_call_records_synced_us():
    ticks = iter(x * 0.001 for x in range(100))
    tr = Tracer()
    us = timed_call(lambda: None, reps=3, warmup=1, tracer=tr,
                    name="bench", clock=lambda: next(ticks))
    assert us == pytest.approx(1000.0)     # 1 ms per tick pair
    spans = tr.find(name="bench")
    assert len(spans) == 3
    assert all(s.attrs["us"] == pytest.approx(1000.0) for s in spans)


# --------------------------------------------------------------------------
# metrics registry
# --------------------------------------------------------------------------

def test_metrics_get_or_create_and_canonical_keys():
    reg = MetricsRegistry()
    c = reg.counter("serve_shed", reason="deadline")
    c.inc()
    c.inc(2.0)
    assert reg.counter("serve_shed", reason="deadline") is c
    assert c.key == "serve_shed{reason=deadline}"
    # label order never matters
    g = reg.gauge("depth", bucket=4, model="vgg")
    assert reg.gauge("depth", model="vgg", bucket=4) is g
    assert g.key == "depth{bucket=4,model=vgg}"
    g.set(3)
    g.inc()
    g.dec(2)
    assert g.snapshot() == 2.0
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("serve_shed", reason="deadline")


def test_histogram_stats_and_quantiles():
    reg = MetricsRegistry()
    h = reg.histogram("lat", bucket=8)
    for v in range(1, 101):
        h.observe(float(v))
    s = h.snapshot()
    assert s["count"] == 100 and s["sum"] == pytest.approx(5050.0)
    assert (s["min"], s["max"]) == (1.0, 100.0)
    assert s["mean"] == pytest.approx(50.5)
    assert s["p50"] == pytest.approx(50.0, abs=1.0)
    assert s["p99"] == pytest.approx(99.0, abs=1.0)
    # bounded reservoir: the window slides, count keeps the truth
    small = reg.histogram("w", window=4)
    for v in (1.0, 2.0, 3.0, 4.0, 100.0):
        small.observe(v)
    assert small.count == 5 and small.quantile(1.0) == 100.0
    assert small.quantile(0.0) == 2.0      # 1.0 slid out


def test_snapshot_find_and_render_are_deterministic():
    reg = MetricsRegistry()
    reg.counter("b").inc()
    reg.gauge("a", bucket=2).set(1.5)
    reg.histogram("c").observe(0.25)
    snap = reg.snapshot()
    assert list(snap) == sorted(snap)
    assert snap["a{bucket=2}"] == 1.5
    assert reg.find("a")== {"a{bucket=2}": 1.5}
    text = reg.render()
    assert "a{bucket=2} 1.5" in text and "c count=1" in text


# --------------------------------------------------------------------------
# export
# --------------------------------------------------------------------------

def test_chrome_trace_shape_and_unfinished_spans():
    ticks = iter(range(100))
    tr = Tracer(clock=lambda: float(next(ticks)))
    with tr.span("done", rid=1):
        tr.event("mark")
    tr.begin("crashed", rid=2)             # never ended
    reg = MetricsRegistry()
    reg.counter("served").inc(3)
    doc = chrome_trace(tr, reg)
    by_ph = {}
    for e in doc["traceEvents"]:
        by_ph.setdefault(e["ph"], []).append(e)
    assert set(by_ph) == {"X", "i", "M"}
    done = next(e for e in by_ph["X"] if e["name"] == "done")
    assert done["ts"] == 0.0 and done["dur"] == 2e6   # us scale
    crashed = next(e for e in by_ph["X"] if e["name"] == "crashed")
    assert crashed["dur"] == 0.0 and crashed["args"]["unfinished"]
    assert by_ph["M"][0]["args"]["name"] == "MainThread"
    assert doc["otherData"]["metrics"]["served"] == 3
    assert doc["otherData"]["dropped_records"] == 0
    # non-JSON attr values survive via repr
    tr.event("odd", shape=(1, 2))
    assert chrome_trace(tr)["traceEvents"][0]  # still serializable
    json.dumps(chrome_trace(tr), sort_keys=True)


def test_events_jsonl_round_trips():
    tr = Tracer()
    with tr.span("a", rid=1):
        tr.event("b")
    lines = events_jsonl(tr).strip().splitlines()
    objs = [json.loads(l) for l in lines]
    assert [o["name"] for o in objs] == ["a", "b"]
    assert objs[1]["parent"] == objs[0]["sid"]


def _chaos_run(seed, submissions=20):
    """One seeded chaos serve with full tracing; deterministic because
    tracer and server share one VirtualClock."""
    clock = VirtualClock()
    tracer = Tracer(clock=clock)
    metrics = MetricsRegistry()
    server = ImageServer(_tiny_params(), 8, 8, compute=False,
                         clock=clock, wait_budget=0.01,
                         tracer=tracer, metrics=metrics)
    loop = ServingLoop(server, deadline_s=0.2,
                       fault_plan=FaultPlan.random(seed,
                                                   service_s=0.02),
                       service_estimate_s=0.02, seed=seed)
    rng = random.Random(seed)
    for _ in range(submissions):
        loop.submit(n_images=rng.randint(1, 8))
        if rng.random() < 0.5:
            loop.pump()
        if rng.random() < 0.3:
            clock.sleep(round(rng.random(), 3) * 0.05)
    loop.run_sync(tick_s=0.01)
    return loop, server, tracer, metrics


@pytest.mark.parametrize("seed", [0, 3, 7])
def test_trace_export_is_bit_identical_per_seed(tmp_path, seed):
    paths = []
    for run in ("a", "b"):
        _, server, tracer, metrics = _chaos_run(seed)
        p = write_trace(tmp_path / f"{run}.json", tracer, metrics)
        paths.append(p)
    a, b = paths
    assert a.read_bytes() == b.read_bytes()
    assert (Path(str(a) + ".jsonl").read_bytes()
            == Path(str(b) + ".jsonl").read_bytes())
    # and it is loadable, non-trivial Chrome trace JSON
    doc = json.loads(a.read_text())
    assert len(doc["traceEvents"]) > 20


# --------------------------------------------------------------------------
# span-tree integrity under chaos (the drop-free invariant, traced)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(6))
def test_chaos_every_rid_has_exactly_one_terminal_span(seed):
    loop, server, tracer, metrics = _chaos_run(seed)
    assert loop.all_terminal()
    c = loop.counters
    spans = tracer.find(name="request")
    assert len(spans) == c["submitted"]
    by_rid = {}
    for sp in spans:
        assert sp.finished, sp
        assert by_rid.setdefault(sp.attrs["rid"], sp) is sp
    terminals = tracer.find(name="request.terminal")
    assert len(terminals) == c["submitted"]
    # each rid's span state matches the loop's terminal state
    for rid, t in loop.requests.items():
        sp = by_rid[rid]
        assert sp.attrs["state"] == t.state.value
    states = [sp.attrs["state"] for sp in spans]
    assert states.count(RequestState.DONE.value) == c["done"]
    assert states.count(RequestState.SHED.value) == c["shed"]
    assert states.count(RequestState.FAILED.value) == c["failed"]
    # the counter metrics reconcile with the ledger exactly
    led = server.ledger.summary()
    snap = metrics.snapshot()
    assert snap.get("serve_served", 0) == led["served_requests"]
    shed = sum(v for k, v in snap.items()
               if k.startswith("serve_shed"))
    assert shed == led["shed_requests"]
    assert snap.get("serve_failed", 0) == led["failed_requests"]


def test_chaos_breaker_and_retry_events_fire_when_counted():
    loop, _, tracer, _ = _chaos_run(3)
    c = loop.counters
    assert len(tracer.find(name="dispatch.retry")) == c["retries"]
    attempts = tracer.find(name="dispatch.attempt")
    assert attempts and all(s.finished for s in attempts)
    assert (sum(s.attrs["outcome"] == "error" for s in attempts)
            == c["retries"] + c["failed"] > 0)


# --------------------------------------------------------------------------
# overhead budget: tracing off must stay ~free
# --------------------------------------------------------------------------

def test_noop_overhead_under_two_percent_of_serve_smoke():
    n = 20000
    t0 = time.perf_counter()
    for i in range(n):
        with NULL_TRACER.span("x", rid=i):
            pass
        NULL_TRACER.event("y", rid=i)
    per_site = (time.perf_counter() - t0) / (2 * n)
    # census: the obs sites one traced smoke actually hits, and the
    # wall time the same smoke costs (virtual service time is free —
    # this is real planning/accounting work)
    w0 = time.perf_counter()
    _, _, tracer, _ = _chaos_run(11)
    wall = time.perf_counter() - w0
    sites = len(tracer.records) + tracer.dropped
    assert sites > 50
    assert sites * per_site < 0.02 * wall, (
        f"{sites} sites x {per_site * 1e6:.2f}us disabled cost vs "
        f"{wall * 1e3:.1f}ms smoke")


# --------------------------------------------------------------------------
# instrumentation through planning / kernels / graphs / serving
# --------------------------------------------------------------------------

def test_plan_search_span_rides_the_ambient_tracer():
    from repro.kernels.conv_lb.ops import plan_conv

    tr = Tracer()
    with tr.activate():
        # a geometry no other test uses: guaranteed lru-cache miss
        plan_conv(19, 19, 5, 7, 3, 3, batch=2)
    (sp,) = tr.find(name="plan.search")
    assert sp.finished and sp.attrs["layer"] == "5->7k3x3"
    assert "blocks" in sp.attrs
    # cached geometry: no new search span
    with tr.activate():
        plan_conv(19, 19, 5, 7, 3, 3, batch=2)
    assert len(tr.find(name="plan.search")) == 1


def test_conv2d_lb_timed_attaches_bytes_and_seconds():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 8, 4))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 4, 4))
    from repro.kernels.conv_lb.ops import conv2d_lb, conv2d_lb_timed

    tr = Tracer()
    out = conv2d_lb_timed(x, w, padding=1, fallback=True, tracer=tr)
    ref = conv2d_lb(x, w, padding=1, fallback=True)
    assert jnp.allclose(out, ref, atol=1e-5)
    (sp,) = tr.find(name="kernel.conv2d_lb")
    assert sp.attrs["mode"] == "lax"
    assert sp.attrs["traffic_bytes"] > 0
    assert sp.attrs["us"] > 0
    assert sp.attrs["achieved_gbps"] == pytest.approx(
        sp.attrs["traffic_bytes"] / (sp.attrs["us"] / 1e6) / 1e9)
    # with no tracer anywhere, the call is still just conv2d_lb
    assert jnp.allclose(conv2d_lb_timed(x, w, padding=1,
                                        fallback=True), ref,
                        atol=1e-5)


def test_graph_forward_emits_per_layer_spans():
    params = _tiny_params()
    g = vgg_graph(params)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 8, 3))
    tr = Tracer()
    graph_forward(g, params["convs"], x, tracer=tr)
    (fwd,) = tr.find(name="graph.forward")
    layers = tr.find(name="graph.layer")
    assert len(layers) == len(g.nodes)
    assert all(s.parent == fwd.sid for s in layers)
    kernels = tr.find(name="kernel.conv2d_lb")
    assert len(kernels) == len(g.nodes)
    assert all(s.attrs["traffic_bytes"] > 0 for s in kernels)
    # under jit tracing, spans must NOT record trace-time garbage
    tr2 = Tracer()
    jax.jit(lambda q: graph_forward(g, params["convs"], q,
                                    tracer=tr2))(x)
    assert tr2.find(name="graph.forward") == []


# --------------------------------------------------------------------------
# per-bucket gauges + ledger summary rendering
# --------------------------------------------------------------------------

def test_per_bucket_gauges_track_backlog_and_inflight():
    clock = VirtualClock()
    server = ImageServer(_tiny_params(), 8, 8, compute=False,
                         clock=clock, wait_budget=10.0)
    loop = ServingLoop(server, deadline_s=60.0)
    loop.submit(n_images=3)               # partial bucket: backlog
    stats = loop.stats
    b = server.queue.bucket_for(3)
    assert stats["backlog_by_bucket"] == {b: 1}
    assert stats["inflight_by_bucket"].get(b, 0) == 0
    assert (server.metrics.gauge("serve_backlog", bucket=b)
            .snapshot() == 1)
    line = server.ledger.format_summary()
    assert f"b{b}: 0 in-flight / 1 backlog" in line
    clock.sleep(11.0)
    loop.pump()
    stats = loop.stats
    assert stats["backlog_by_bucket"] == {}
    assert all(v == 0 for v in stats["inflight_by_bucket"].values())
    # drained: the gauge line disappears rather than printing zeros
    assert "backlog" not in server.ledger.format_summary()


# --------------------------------------------------------------------------
# serving spans on the profiler's clock
# --------------------------------------------------------------------------

def _served(tracer):
    """A tiny computing serve run (lax pipelines) on ``tracer``."""
    server = ImageServer(_tiny_params(), 8, 8, target="lax",
                         wait_budget=0.0, tracer=tracer)
    loop = ServingLoop(server, deadline_s=None)
    images = jnp.ones((3, 8, 8, 3))
    loop.submit(images[:1])
    loop.submit(images)
    loop.run_sync()
    return loop


def test_untraced_serving_enters_no_annotation(annotations):
    loop = _served(None)
    assert loop.counters["done"] == 2
    assert annotations == []


def test_traced_serving_annotates_where_the_work_happens(annotations):
    tracer = Tracer()
    loop = _served(tracer)
    assert loop.counters["done"] == 2
    for name in ("loop.admit", "serve.h2d", "serve.assemble",
                 "serve.execute", "serve.fetch", "serve.complete"):
        assert name in annotations, name
        assert all(s.finished for s in tracer.find(name=name))
    # the copy sits inside the admission's locked section
    (admit, _), h2d = (tracer.find(name="loop.admit"),
                       tracer.find(name="serve.h2d"))
    assert h2d[0].parent == admit.sid
    assert [s.attrs["n_images"] for s in h2d] == [1, 3]
    assert all(s.attrs["bucket"] in (1, 4)
               for s in tracer.find(name="serve.complete"))
    # one host copy of each computing dispatch's whole (bucket, 4
    # classes) float32 logits, after the device call's own span
    fetch, execute = (tracer.find(name="serve.fetch"),
                      tracer.find(name="serve.execute"))
    assert len(fetch) == len(execute) == loop.server.stats["dispatches"]
    assert [s.attrs["bytes"] for s in fetch] == [
        s.attrs["bucket"] * 4 * 4 for s in fetch]
    assert all(f.t0 >= e.t1 for f, e in zip(fetch, execute))
    # the per-request instant events are gone: spans replace them
    assert not tracer.find(name="serve.admit")
    assert not [s for s in tracer.find(name="serve.complete")
                if s.kind == "instant"]
    assert not tracer.find(name="plan.cache_hit")


# --------------------------------------------------------------------------
# --trace drivers end to end
# --------------------------------------------------------------------------

def test_example_serve_images_trace_flag(tmp_path, monkeypatch, capsys):
    out = tmp_path / "serve.json"
    mod = _load(REPO / "examples" / "serve_images.py")
    monkeypatch.setattr(sys, "argv",
                        ["serve_images.py", "--account-only",
                         "--requests", "5", "--deadline", "0.5",
                         "--fault-plan", "random:3",
                         "--trace", str(out)])
    mod.main()
    assert "trace:" in capsys.readouterr().out
    doc = json.loads(out.read_text())
    events = doc["traceEvents"]
    assert {e["ph"] for e in events} <= {"X", "i", "M"}
    terminals = [e for e in events
                 if e["name"] == "request.terminal"]
    assert len(terminals) == 5
    # terminal states in the trace reconcile with the ledger exactly
    by_state = {}
    for e in terminals:
        s = e["args"]["state"]
        by_state[s] = by_state.get(s, 0) + 1
    led = doc["otherData"]["metrics"]
    served = led.get("serve_served", 0)
    assert by_state.get("done", 0) == served
    jsonl = Path(str(out) + ".jsonl")
    assert jsonl.exists()
    assert all(json.loads(l)
               for l in jsonl.read_text().splitlines())


def test_example_train_vgg_trace_flag(tmp_path, monkeypatch, capsys):
    out = tmp_path / "train.json"
    mod = _load(REPO / "examples" / "train_vgg.py")
    monkeypatch.setattr(sys, "argv",
                        ["train_vgg.py", "--steps", "1",
                         "--batch", "2", "--image", "8",
                         "--trace", str(out)])
    mod.main()
    assert "trace:" in capsys.readouterr().out
    doc = json.loads(out.read_text())
    names = {e["name"] for e in doc["traceEvents"]}
    assert "train.step" in names
    assert "graph.training_report" in names
    # leaving main() must deactivate the ambient tracer
    assert active_tracer() is NULL_TRACER
