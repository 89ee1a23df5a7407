"""Bucketed image-serving subsystem: admission, plan/jit caching,
deadline flush, per-request traffic ledger, and the serving-scale
acceptance numbers (Eq. (15) attainment + weight-read amortization).

The paper-scale assertions run the server in account-only mode
(planning + ledger without compute) so the full VGG16/224x224 serving
geometry is exercised in milliseconds; the compute-path tests use a
reduced-width stack on tiny images through the real interpret-mode
kernel pipelines.
"""

import importlib.util
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.lower_bound import q_dram_practical, q_dram_serving
from repro.core.vgg import vgg16_conv_layers
from repro.kernels.conv_lb.ops import (conv_lb_traffic,
                                       conv_lb_traffic_bytes, plan_conv)
from repro.models.cnn import (init_resnet, init_vgg, resnet_graph,
                              vgg_conv_geometry, vgg_plan_handles)
from repro.models.graph import graph_logits
from repro.serve import AdmissionQueue, ImageRequest, ImageServer, bucket_for

REPO = Path(__file__).resolve().parent.parent


def _load(path: Path):
    spec = importlib.util.spec_from_file_location(path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# --------------------------------------------------------------------------
# bucketed admission
# --------------------------------------------------------------------------

def test_bucket_for_ladder():
    assert bucket_for(1) == 1
    assert bucket_for(2) == 2
    assert bucket_for(3) == 4
    assert bucket_for(5) == 8
    with pytest.raises(ValueError):
        bucket_for(9, (1, 2, 4, 8))


def test_full_bucket_dispatches_immediately():
    q = AdmissionQueue(buckets=(1, 2, 4), wait_budget=10.0)
    for rid, n in enumerate((1, 2, 1)):
        q.submit(ImageRequest(rid=rid, n_images=n, arrival=0.0))
    group, bucket = q.pop_ready(now=0.0)     # 1+2+1 == max bucket
    assert bucket == 4
    assert [r.rid for r in group] == [0, 1, 2]
    assert q.pop_ready(now=0.0) is None      # queue drained


def test_maximal_group_dispatches_without_waiting():
    """FIFO prefix that can no longer grow (next request would
    overflow) dispatches at once — waiting cannot improve it."""
    q = AdmissionQueue(buckets=(1, 2, 4, 8), wait_budget=10.0)
    q.submit(ImageRequest(rid=0, n_images=5, arrival=0.0))
    q.submit(ImageRequest(rid=1, n_images=4, arrival=0.0))
    group, bucket = q.pop_ready(now=0.0)
    assert [r.rid for r in group] == [0]     # 5+4 > 8: head goes alone
    assert bucket == 8                       # padded 5 -> 8
    assert q.pop_ready(now=0.0) is None      # [4] waits for company


def test_flush_on_deadline_dispatches_partial_bucket():
    q = AdmissionQueue(buckets=(1, 2, 4, 8), wait_budget=0.05)
    q.submit(ImageRequest(rid=0, n_images=3, arrival=0.0))
    assert q.pop_ready(now=0.01) is None     # within the wait budget
    group, bucket = q.pop_ready(now=0.06)    # oldest overdue: flush
    assert [r.rid for r in group] == [0]
    assert bucket == 4                       # smallest covering bucket


def test_mixed_arrival_sizes_pad_to_right_bucket():
    """Server-level: charges record the covering bucket and the ledger
    counts the padding images the bucketing cost."""
    params = init_vgg(jax.random.PRNGKey(0), n_classes=4,
                      width_mult=0.05)
    t = [0.0]
    srv = ImageServer(params, 8, 8, compute=False, clock=lambda: t[0],
                      wait_budget=0.05)
    srv.submit(n_images=3, now=0.0)          # -> bucket 4, 1 pad
    assert srv.poll(now=0.0) == []           # not overdue, not maximal
    t[0] = 0.1
    results = srv.poll(now=t[0])             # deadline flush: 3 -> 4
    srv.submit(n_images=5, now=t[0])         # -> bucket 8, 3 pad
    assert srv.poll(now=t[0]) == []
    t[0] = 0.2
    results += srv.poll(now=t[0])            # deadline flush: 5 -> 8
    assert [r.charge.bucket for r in results] == [4, 8]
    assert srv.ledger.padded_images == 4
    # padding is charged to the real requests: the request's bytes are
    # the whole dispatch's bytes (it is alone in its group)
    for r, handles in zip(results, (srv.plan_handles(4),
                                    srv.plan_handles(8))):
        whole = sum(p.traffic(r.charge.bucket).total for _, p in handles)
        assert r.charge.bytes_total == pytest.approx(whole * 4)


def test_result_and_charge_retention_is_bounded():
    """Long-serving processes: the results window and the ledger's
    per-request records are bounded; aggregates keep counting."""
    params = init_vgg(jax.random.PRNGKey(0), n_classes=4,
                      width_mult=0.05)
    t = [0.0]
    srv = ImageServer(params, 8, 8, compute=False, clock=lambda: t[0],
                      wait_budget=0.0, keep_results=2)
    srv.ledger.charges = type(srv.ledger.charges)(maxlen=2)
    rids = []
    for _ in range(5):                   # one dispatch per request —
        rids.append(srv.submit(n_images=1, now=0.0))
        srv.poll(now=0.0)                # in-group results never evict
    assert set(srv.results) == set(rids[-2:])   # oldest evicted
    assert srv.stats["results_evicted"] == 3
    assert len(srv.ledger.charges) == 2
    s = srv.ledger.summary()
    assert s["requests"] == 5 and s["images"] == 5  # aggregates intact


def test_oversized_request_rejected():
    q = AdmissionQueue(buckets=(1, 2, 4), wait_budget=0.0)
    with pytest.raises(ValueError):
        q.submit(ImageRequest(rid=0, n_images=5, arrival=0.0))


def test_queue_bucket_for_handles_unsorted_ladders():
    """Regression: the queue's bucket_for walks the ladder sorted once
    at construction — an unsorted custom ladder must not mis-bucket
    (the module-level one-shot re-sorts per call)."""
    q = AdmissionQueue(buckets=(8, 2, 4, 1), wait_budget=0.0)
    assert q.buckets == (1, 2, 4, 8)
    assert [q.bucket_for(n) for n in (1, 2, 3, 5, 8)] == [1, 2, 4, 8, 8]
    with pytest.raises(ValueError):
        q.bucket_for(9)
    assert bucket_for(3, (8, 2, 4, 1)) == 4  # one-shot API agrees


def test_stats_exposes_live_queue_gauges():
    """`stats` carries live health gauges, not just counters: queue
    depth and head-of-line wait move with the queue (and the wait is
    clamped >= 0 under a rewound clock)."""
    params = init_vgg(jax.random.PRNGKey(0), n_classes=4,
                      width_mult=0.05)
    t = [1.0]
    srv = ImageServer(params, 8, 8, compute=False, clock=lambda: t[0],
                      wait_budget=10.0)
    assert srv.stats["queue_depth"] == 0
    assert srv.stats["oldest_wait_s"] == 0.0
    srv.submit(n_images=1, now=1.0)
    srv.submit(n_images=2, now=1.0)
    t[0] = 1.5
    assert srv.stats["queue_depth"] == 2
    assert srv.stats["oldest_wait_s"] == pytest.approx(0.5)
    t[0] = 0.25                              # clock skewed backwards
    assert srv.stats["oldest_wait_s"] == 0.0
    t[0] = 20.0
    srv.poll(now=t[0])
    assert srv.stats["queue_depth"] == 0
    assert srv.stats["oldest_wait_s"] == 0.0


def test_tiny_results_window_never_evicts_current_dispatch():
    """Regression: with keep_results smaller than a dispatch group,
    eviction must skip the results that dispatch just produced — naive
    oldest-first trimming would hand the caller rids whose results are
    already gone."""
    params = init_vgg(jax.random.PRNGKey(0), n_classes=4,
                      width_mult=0.05)
    t = [0.0]
    srv = ImageServer(params, 8, 8, compute=False, clock=lambda: t[0],
                      wait_budget=0.0, keep_results=1, buckets=(4,))
    rids = [srv.submit(n_images=1, now=0.0) for _ in range(4)]
    results = srv.poll(now=0.0)              # one group of 4 requests
    assert [r.rid for r in results] == rids
    assert set(srv.results) == set(rids)     # all 4 survive eviction
    assert srv.stats["results_evicted"] == 0
    late = srv.submit(n_images=4, now=0.0)
    srv.poll(now=0.0)                        # next dispatch may evict
    assert set(srv.results) == {late}
    assert srv.stats["results_evicted"] == 4


# --------------------------------------------------------------------------
# per-bucket plan + jit cache (compute path, real kernel pipelines)
# --------------------------------------------------------------------------

def test_same_bucket_hits_plan_and_jit_cache():
    """Second dispatch of the same bucket: no re-plan (plan_conv cache
    untouched), no re-trace (trace counter flat), pipeline served from
    the per-bucket cache."""
    params = init_vgg(jax.random.PRNGKey(0), n_classes=4,
                      width_mult=0.05)
    srv = ImageServer(params, 8, 8, buckets=(2,), wait_budget=0.0)
    key = jax.random.PRNGKey(1)
    srv.submit(jax.random.normal(key, (2, 8, 8, 3)))
    first = srv.poll()
    assert len(first) == 1 and first[0].logits.shape == (2, 4)
    assert srv.stats["traces"] == 1
    misses0 = plan_conv.cache_info().misses
    traces0 = srv.stats["traces"]
    srv.submit(jax.random.normal(jax.random.fold_in(key, 1), (2, 8, 8, 3)))
    second = srv.poll()
    assert len(second) == 1 and second[0].logits.shape == (2, 4)
    assert srv.stats["traces"] == traces0                  # no re-trace
    assert plan_conv.cache_info().misses == misses0        # no re-plan
    assert srv.stats["pipeline_hits"] >= 1
    assert srv.stats["plan_hits"] >= 1
    # different results for different inputs (the pipeline really ran)
    assert not jnp.allclose(first[0].logits, second[0].logits)


def test_plan_handle_cache_keyed_by_image_geometry():
    """Regression: the plan-handle cache is keyed by (graph, bucket,
    image geometry, word size), not the bucket alone — a server whose
    serving geometry is re-pointed must never silently reuse plans for
    the old image size."""
    params = init_vgg(jax.random.PRNGKey(0), n_classes=4,
                      width_mult=0.05)
    srv = ImageServer(params, 8, 8, compute=False, wait_budget=0.0)
    h8 = srv.plan_handles(2)
    assert h8[0][0].hi == 8
    srv.h = srv.w = 16                   # re-pointed serving geometry
    h16 = srv.plan_handles(2)
    assert h16 is not h8
    assert h16[0][0].hi == 16            # fresh plans, not stale 8x8
    assert h16[0][1].traffic(2).total != h8[0][1].traffic(2).total
    srv.h = srv.w = 8                    # ...and the old geometry's
    assert srv.plan_handles(2) is h8     # handles stayed warm


def test_kernel_and_fallback_pipelines_agree():
    """The bucketed kernel pipeline computes the same logits as the
    lax fallback server on identical inputs."""
    params = init_vgg(jax.random.PRNGKey(0), n_classes=4,
                      width_mult=0.05)
    imgs = jax.random.normal(jax.random.PRNGKey(2), (2, 8, 8, 3))
    out = {}
    for target in ("interpret", "lax"):
        srv = ImageServer(params, 8, 8, buckets=(2,), wait_budget=0.0,
                          target=target)
        srv.submit(imgs)
        out[target] = srv.poll()[0].logits
    assert jnp.allclose(out["interpret"], out["lax"], atol=2e-4)


# --------------------------------------------------------------------------
# answers from one host copy of each dispatch's logits
# --------------------------------------------------------------------------

def _mixed_group(srv, sizes, key):
    """Queue requests of ``sizes`` images on ``srv``; returns their
    images stacked in submission order."""
    imgs = jax.random.normal(key, (sum(sizes), 8, 8, 3))
    off = 0
    for n in sizes:
        srv.submit(imgs[off:off + n], now=0.0)
        off += n
    return imgs


def test_dispatch_answers_host_slices_of_one_copy():
    """A group of sizes [2, 1, 3] padded into bucket 8: each request
    gets its own rows of the pipeline's output as a host array, the
    padding rows are dropped, and the dispatch took one host copy."""
    params = init_vgg(jax.random.PRNGKey(0), n_classes=4,
                      width_mult=0.05)
    srv = ImageServer(params, 8, 8, buckets=(8,), wait_budget=0.0,
                      target="lax")
    imgs = _mixed_group(srv, [2, 1, 3], jax.random.PRNGKey(3))
    results = srv.poll(now=1.0)
    padded = jnp.pad(imgs, ((0, 2), (0, 0), (0, 0), (0, 0)))
    rows = np.asarray(srv.pipeline(8)(params, padded))
    assert [r.charge.bucket for r in results] == [8, 8, 8]
    assert all(type(r.logits) is np.ndarray for r in results)
    assert [r.logits.shape for r in results] == [(2, 4), (1, 4), (3, 4)]
    np.testing.assert_array_equal(
        np.concatenate([r.logits for r in results]), rows[:6])
    assert srv.stats["host_copies"] == srv.stats["dispatches"] == 1
    assert (srv.metrics.counter("serve_logits_d2h_bytes").snapshot()
            == rows.nbytes == 8 * 4 * 4)


def test_account_only_dispatch_takes_no_host_copy():
    params = init_vgg(jax.random.PRNGKey(0), n_classes=4,
                      width_mult=0.05)
    srv = ImageServer(params, 8, 8, buckets=(8,), wait_budget=0.0,
                      compute=False)
    for n in (2, 1, 3):
        srv.submit(n_images=n, now=0.0)
    results = srv.poll(now=1.0)
    assert len(results) == 3
    assert all(r.logits is None for r in results)
    assert srv.stats["dispatches"] == 1
    assert srv.stats["host_copies"] == 0
    assert srv.metrics.counter("serve_logits_d2h_bytes").snapshot() == 0


def test_completion_of_a_new_group_compiles_nothing():
    """Once a bucket is warm, answering a group whose composition was
    never seen before compiles nothing: ``_complete`` slices the host
    copy and runs no device op (an eager device slice per request
    would compile one program per new request size)."""
    params = init_vgg(jax.random.PRNGKey(0), n_classes=4,
                      width_mult=0.05)
    srv = ImageServer(params, 8, 8, buckets=(8,), wait_budget=0.0,
                      target="lax")
    srv.warm()
    _mixed_group(srv, [5, 2], jax.random.PRNGKey(4))
    group, bucket = srv.queue.pop_ready(1.0)
    logits = srv._execute(group, bucket)
    compiles = []

    def on_event(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(kw.get("fun_name"))

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        results = srv._complete(group, bucket, logits, now=1.0)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert [r.logits.shape for r in results] == [(5, 4), (2, 4)]
    assert compiles == []


# --------------------------------------------------------------------------
# acceptance: serving-scale traffic economics (account-only, VGG16)
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def vgg16_server():
    params = init_vgg(jax.random.PRNGKey(0), n_classes=10,
                      width_mult=1.0)
    t = [0.0]
    srv = ImageServer(params, 224, 224, compute=False,
                      clock=lambda: t[0], wait_budget=0.05)
    # N=16 mixed-size requests, FIFO-packing into four full 8-buckets
    for n in (1, 2, 1, 4, 2, 1, 1, 4, 2, 1, 3, 2, 1, 2, 4, 1):
        srv.submit(n_images=n, now=0.0)
    srv.poll(now=0.0)
    srv.drain(now=0.0)
    return srv


def test_serving_mixed16_amortizes_weight_reads_4x(vgg16_server):
    """Acceptance: N=16 mixed-size requests through the bucketed
    server read >= 4x fewer accounted weight bytes per request than
    batch=1 dispatch (the pre-batch-fold per-image planner) on the
    VGG16 stack."""
    s = vgg16_server.ledger.summary()
    assert s["requests"] == 16
    assert s["dispatches"] == 4              # four full 8-buckets
    assert s["padded_images"] == 0
    assert s["w_amortization_x"] >= 4.0, s


def test_serving_mixed16_attains_eq15_per_request(vgg16_server):
    """Acceptance: every request's accounted bytes stay within 1.25x
    of its Eq. (15) share at the 1 MiB accounting budget."""
    charges = vgg16_server.ledger.charges
    assert len(charges) == 16
    for c in charges:
        assert c.vs_bound_x <= 1.25, (c.rid, c.vs_bound_x)
    s = vgg16_server.ledger.summary()
    assert s["vs_bound_x"] <= 1.25
    # the serving-horizon bound (weights amortized over the horizon)
    # is tighter than per-dispatch Eq. (15), never looser
    assert s["vs_serving_x"] >= 0.95 * s["vs_bound_x"]


# --------------------------------------------------------------------------
# cross-model serving: ResNet through the same bucketed ledger path
# --------------------------------------------------------------------------

def test_server_serves_resnet_end_to_end():
    """A ResNet BasicBlock stack (stride-2 downsampling, 1x1
    projection shortcuts, fused residual joins) serves through the
    same ImageServer: kernel pipeline logits match the direct lax
    forward, and the ledger reports a per-model vs-bound row."""
    graph = resnet_graph(blocks=(1, 1), widths=(4, 8), name="rn-serve")
    params = init_resnet(jax.random.PRNGKey(0), graph, n_classes=4)
    srv = ImageServer(params, 8, 8, graph=graph, buckets=(2,),
                      wait_budget=0.0)
    imgs = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 8, 3))
    srv.submit(imgs)
    (res,) = srv.poll()
    assert res.logits.shape == (2, 4)
    ref = graph_logits(graph, params, imgs, target="lax")
    assert jnp.allclose(res.logits, ref, atol=2e-4)
    s = srv.ledger.summary()
    assert "rn-serve" in s["by_model"]
    row = s["by_model"]["rn-serve"]
    assert row["images"] == 2 and row["vs_bound_x"] > 0


def test_resnet_account_only_serving_within_bound():
    """Acceptance: full-width ResNet-20 at CIFAR geometry through the
    account-only bucketed server lands <= 1.25x the per-graph
    Eq. (15) sum at the 1 MiB budget, per request and per model."""
    graph = resnet_graph()
    params = init_resnet(jax.random.PRNGKey(0), graph, n_classes=10)
    t = [0.0]
    srv = ImageServer(params, 32, 32, graph=graph, compute=False,
                      clock=lambda: t[0], wait_budget=0.05)
    for n in (1, 2, 1, 4, 2, 1, 1, 4):     # two full 8-buckets
        srv.submit(n_images=n, now=0.0)
    srv.poll(now=0.0)
    srv.drain(now=0.0)
    s = srv.ledger.summary()
    assert s["dispatches"] == 2 and s["padded_images"] == 0
    for c in srv.ledger.charges:
        assert c.vs_bound_x <= 1.25, (c.rid, c.vs_bound_x)
    assert s["by_model"]["resnet20"]["vs_bound_x"] <= 1.25
    assert s["vs_bound_x"] <= 1.25


def test_mixed_model_ledger_reports_per_model_rows():
    """One ledger fed by two servers (VGG + ResNet) keeps per-model
    vs-bound rows apart while the global aggregates cover both."""
    vgg_p = init_vgg(jax.random.PRNGKey(0), n_classes=4,
                     width_mult=0.05)
    rn_g = resnet_graph(blocks=(1, 1), widths=(4, 8), name="rn-mixed")
    rn_p = init_resnet(jax.random.PRNGKey(1), rn_g, n_classes=4)
    t = [0.0]
    vgg_srv = ImageServer(vgg_p, 8, 8, compute=False,
                          clock=lambda: t[0], wait_budget=0.0)
    rn_srv = ImageServer(rn_p, 8, 8, graph=rn_g, compute=False,
                         clock=lambda: t[0], wait_budget=0.0)
    rn_srv.ledger = vgg_srv.ledger          # shared fleet ledger
    vgg_srv.submit(n_images=2, now=0.0)
    vgg_srv.poll(now=0.0)
    rn_srv.submit(n_images=4, now=0.0)
    rn_srv.poll(now=0.0)
    s = vgg_srv.ledger.summary()
    assert set(s["by_model"]) == {"vgg", "rn-mixed"}
    assert s["by_model"]["vgg"]["images"] == 2
    assert s["by_model"]["rn-mixed"]["images"] == 4
    assert s["images"] == 6
    assert "[rn-mixed]" in vgg_srv.ledger.format_summary()


def test_vgg_plan_handles_match_geometry():
    """Exported plan handles walk exactly the stages vgg_forward runs,
    with pool fused where the plane allows it."""
    params = init_vgg(jax.random.PRNGKey(0), n_classes=10,
                      width_mult=0.1)
    stages = vgg_conv_geometry(params, 32, 32)
    handles = vgg_plan_handles(params, 32, 32, batch=4,
                               vmem_budget=1 << 20)
    assert len(handles) == len(stages) == 13
    for (layer, plan), g in zip(handles, stages):
        assert (layer.hi, layer.wi) == (g.h, g.w)
        assert layer.batch == 4
        assert plan.pool == (2 if g.fused_pool else 1)
        # per-plan traffic surface agrees with the accountant
        t, _ = conv_lb_traffic(4, g.h, g.w, g.ci, g.co, 3, 3,
                               stride=1, padding=1,
                               pool=2 if g.fused_pool else 1,
                               vmem_budget=1 << 20)
        assert plan.traffic(4).total == t.total


# --------------------------------------------------------------------------
# dtype-aware accounting + serving-horizon bound
# --------------------------------------------------------------------------

def test_traffic_bytes_infers_dtype():
    layer = vgg16_conv_layers(batch=2)[4]
    kw = dict(stride=layer.stride, padding=layer.pad,
              vmem_budget=1 << 20)
    args = (layer.batch, layer.hi, layer.wi, layer.ci, layer.co,
            layer.hk, layer.wk)
    b_f32 = conv_lb_traffic_bytes(*args, **kw)
    b_bf16 = conv_lb_traffic_bytes(*args, dtype=jnp.bfloat16, **kw)
    t2, _ = conv_lb_traffic(*args, dtype_bytes=2, **kw)
    assert b_f32 == conv_lb_traffic_bytes(*args, dtype_bytes=4, **kw)
    assert b_bf16 == t2.total * 2            # bf16 words at 2 bytes
    assert b_bf16 < b_f32                    # cheaper serving dtype


def test_ledger_accounts_bf16_serving():
    """A bf16 server charges 2-byte words: same plan handles -> half
    the bytes of the f32 ledger for identical word volume."""
    params = init_vgg(jax.random.PRNGKey(0), n_classes=4,
                      width_mult=0.05)
    charges = {}
    for dtype in (jnp.float32, jnp.bfloat16):
        t = [0.0]
        srv = ImageServer(params, 8, 8, compute=False, dtype=dtype,
                          clock=lambda: t[0], wait_budget=0.0)
        srv.submit(n_images=4, now=0.0)
        (res,) = srv.poll(now=0.0)
        words = sum(p.traffic(4).total
                    for _, p in srv.plan_handles(4))
        assert res.charge.bytes_total == pytest.approx(
            words * jnp.dtype(dtype).itemsize)
        charges[jnp.dtype(dtype).name] = res.charge
    assert (charges["bfloat16"].bytes_total
            < charges["float32"].bytes_total)


def test_q_dram_serving_amortizes_weights():
    layer = vgg16_conv_layers(batch=1)[-1]   # weight-heavy late layer
    s = 256 * 1024 // 4
    per_dispatch = q_dram_practical(layer, s)
    assert q_dram_serving(layer, s, requests=1) == per_dispatch
    horizon = [q_dram_serving(layer, s, requests=n)
               for n in (1, 8, 64, 4096)]
    assert horizon == sorted(horizon, reverse=True)  # monotone down
    # floor: per-image inputs+outputs can never amortize away
    floor = (layer.ci * layer.hi * layer.wi
             + layer.co * layer.ho * layer.wo)
    assert horizon[-1] >= floor


# --------------------------------------------------------------------------
# smoke: serve examples stay collected + runnable in-process
# --------------------------------------------------------------------------

def test_example_serve_images_smoke(monkeypatch, capsys):
    mod = _load(REPO / "examples" / "serve_images.py")
    monkeypatch.setattr(sys, "argv",
                        ["serve_images.py", "--requests", "3",
                         "--image", "8", "--width-mult", "0.05"])
    mod.main()
    out = capsys.readouterr().out
    assert "ledger:" in out and "vs Eq.(15) bound" in out


def test_example_serve_images_resnet_smoke(monkeypatch, capsys):
    """--model resnet rides the same CLI path (compute, tiny stack)."""
    mod = _load(REPO / "examples" / "serve_images.py")
    monkeypatch.setattr(sys, "argv",
                        ["serve_images.py", "--model", "resnet",
                         "--requests", "2", "--image", "8",
                         "--width-mult", "0.25"])
    mod.main()
    out = capsys.readouterr().out
    assert "ledger:" in out and "[resnet20]" in out


def test_launch_serve_images_cli_smoke(monkeypatch, capsys):
    """The launch/ driver end to end in account-only mode (paper-scale
    geometry, no compute)."""
    from repro.launch import serve_images
    monkeypatch.setattr(sys, "argv",
                        ["serve_images", "--account-only",
                         "--width-mult", "1.0", "--image", "224",
                         "--requests", "6"])
    serve_images.main()
    out = capsys.readouterr().out
    assert "weight amortization" in out
    assert "served 6 requests" in out


def test_launch_serve_images_resnet_cli_smoke(monkeypatch, capsys):
    """The launch/ driver serves ResNet account-only at full width."""
    from repro.launch import serve_images
    monkeypatch.setattr(sys, "argv",
                        ["serve_images", "--model", "resnet",
                         "--account-only", "--width-mult", "1.0",
                         "--image", "32", "--requests", "6"])
    serve_images.main()
    out = capsys.readouterr().out
    assert "[resnet20]" in out and "served 6 requests" in out


def test_diff_bench_gates_regressions(tmp_path):
    """diff_bench: >10% regressions (in either metric direction) exit
    nonzero; improvements and single records pass."""
    db = _load(REPO / "benchmarks" / "diff_bench.py")

    def record(name, rows):
        import json
        p = tmp_path / name
        p.write_text(json.dumps(
            [{"name": n, "us_per_call": 0.0, "derived": v}
             for n, v in rows]))
        return str(p)

    old = record("BENCH_1.json", [("k/vs_bound_x", 1.0),
                                  ("k/w_reduction_x", 4.0)])
    good = record("BENCH_2.json", [("k/vs_bound_x", 1.05),
                                   ("k/w_reduction_x", 4.2)])
    bad = record("BENCH_3.json", [("k/vs_bound_x", 1.3),
                                  ("k/w_reduction_x", 4.0)])
    worse_w = record("BENCH_4.json", [("k/vs_bound_x", 1.0),
                                      ("k/w_reduction_x", 3.0)])
    assert db.main([old]) == 0               # single record: baseline
    assert db.main([old, good]) == 0         # within tolerance
    assert db.main([old, bad]) == 1          # vs_bound_x up 30%
    assert db.main([old, worse_w]) == 1      # w_reduction_x down 25%
    assert db.main([str(tmp_path / "missing.json")]) == 2


def test_committed_bench_records_pass_gate():
    """The repo's own committed BENCH_*.json records must satisfy the
    regression gate (ROADMAP: traffic regression tracking)."""
    db = _load(REPO / "benchmarks" / "diff_bench.py")
    # numeric order: lexicographic would misplace BENCH_10 before BENCH_2
    records = [str(p) for p in sorted(REPO.glob("BENCH_*.json"),
                                      key=db._bench_index)]
    assert records, "commit a BENCH_<n>.json via benchmarks/run.py --json"
    assert db.main(records) == 0
