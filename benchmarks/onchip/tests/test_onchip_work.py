"""Operations and bytes from the configurations' layer tables, and the
traffic generators."""

import asyncio
import json
import math
import threading
import time

import numpy as np
import pytest

import onchip_tiny  # noqa: F401  (puts the harness on the path)
from harness import spec, traffic, work
from harness.serve_cell import Driver


def config(name):
    return json.loads((spec.HERE / "configs" / f"{name}.json").read_text())


def test_vgg16_224_forward_is_30_7_gflop_per_image():
    cfg = config("vgg16_224")
    conv = 2.0 * sum(work.conv_macs(l) for l in cfg["layers"])
    assert conv == pytest.approx(30.69e9, rel=1e-3)
    assert work.forward_flops(cfg) == conv + 2 * 512 * 1000


def test_resnet20_32_forward_is_81_6_mflop_per_image():
    cfg = config("resnet20_32")
    assert work.forward_flops(cfg) == pytest.approx(81.6e6, rel=2e-3)


def test_vgg16_224_training_step_at_batch_64_is_5_88_tflop():
    cfg = config("vgg16_224")
    assert 64 * work.train_flops(cfg) == pytest.approx(5.88e12, rel=2e-3)


def test_roofline_takes_the_larger_bound_per_pass():
    cfg = config("vgg16_224")
    fwd = work.pass_work(cfg, 8, ("fwd",))
    assert [w["layer"] for w in fwd] == [l["name"] for l in cfg["layers"]]
    peak = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t = work.roofline_seconds(fwd, peak)
    assert t >= sum(w["flops"] for w in fwd) / peak["flops_per_s"]
    assert t >= sum(w["bytes"] for w in fwd) / peak["hbm_bytes_per_s"]
    # conv1_1 reads 8 images and writes 8 x 224 x 224 x 64 words
    assert fwd[0]["bytes"] == 4 * (8 * 224 * 224 * 3 + 27 * 64 + 64
                                   + 8 * 224 * 224 * 64)
    passes = work.pass_work(cfg, 8, ("fwd", "wgrad", "dgrad"))
    assert len(passes) == 3 * 13 - 1          # no dgrad of conv1_1


def test_poisson_offsets_are_seeded_and_hold_the_rate():
    a = traffic.poisson_offsets(800, 10, 7)
    assert np.array_equal(a, traffic.poisson_offsets(800, 10, 7))
    assert not np.array_equal(a, traffic.poisson_offsets(800, 10, 2 ** 33))
    assert len(a) == 8000 and a[0] == 0 and a[-1] < 10
    # every seed: the same gaps in another order, rate x seconds sends
    g1 = traffic.poisson_gaps(800, 10, 7)
    g2 = traffic.poisson_gaps(800, 10, 2 ** 33 + 5)
    assert np.array_equal(np.sort(g1), np.sort(g2))
    assert g1.sum() == pytest.approx(10)
    assert np.median(g1) == pytest.approx(math.log(2) / 800, rel=0.02)
    assert np.allclose(np.diff(a), g1[:-1])


def test_request_images_and_pool_are_seeded():
    tr = {"pool_images": 16}
    cfg = {"image": [4, 4, 3]}
    rows = traffic.request_rows(tr, 50, 4, 3)
    assert np.array_equal(rows, traffic.request_rows(tr, 50, 4, 3))
    assert rows.min() >= 0 and rows.max() <= 16 - 4
    assert np.array_equal(traffic.image_pool(tr, cfg, 3),
                          traffic.image_pool(tr, cfg, 3))
    assert not np.array_equal(traffic.image_pool(tr, cfg, 3),
                              traffic.image_pool(tr, cfg, 4))


def test_request_sizes_are_the_same_set_for_every_seed():
    tr = {"images_per_request": [[1, 3], [2, 1], [8, 0.5]]}
    a = traffic.request_sizes(tr, 900, 7)
    b = traffic.request_sizes(tr, 900, 2 ** 33 + 1)
    assert not np.array_equal(a, b)
    assert np.array_equal(np.sort(a), np.sort(b))
    assert np.bincount(a).tolist() == [0, 600, 200, 0, 0, 0, 0, 0, 100]
    assert traffic.request_sizes({}, 5, 1).tolist() == [1] * 5


def test_train_ring_rows_all_differ():
    tr = {"ring_batches": 3, "batch": 4}
    images, labels = traffic.train_ring(tr, {"image": [4, 4, 3],
                                             "classes": 10}, 9)
    assert images.shape == (3, 4, 4, 4, 3) and labels.shape == (3, 4)
    flat = images.reshape(12, -1)
    assert len({r.tobytes() for r in flat}) == 12


@pytest.mark.parametrize("q,lat,want", [
    (99, [0.001 * i for i in range(1, 101)], 0.001 * 99),
    (95, [0.001 * i for i in range(1, 101)], 0.001 * 95),
    (99, [0.001] * 99 + [None], 0.001),
    (99, [0.001] * 98 + [None, None], math.inf),
    (95, [0.001] * 94 + [None] * 6, math.inf),
])
def test_tail_counts_misses_as_infinite(q, lat, want):
    assert traffic.percentile(lat, q) == want


class FakeLoop:
    """A serving loop that answers each request `service_s` after it."""

    def __init__(self, service_s):
        self.service_s = service_s
        self.requests = {}
        self.images = []
        self._lock = threading.RLock()

    def submit(self, images):
        rid = len(self.requests)
        self.images.append(len(images))
        done_at = time.monotonic() + self.service_s
        self.requests[rid] = type("Req", (), {
            "terminal": property(lambda _: time.monotonic() >= done_at)})()
        return rid

    async def run_async(self, until_idle=True):
        while True:
            await asyncio.sleep(0.001)


def test_closed_loop_keeps_its_clients_outstanding():
    loop = FakeLoop(0.02)
    plan = traffic.Plan(sizes=np.array([1, 2]), clients=4)
    drv = Driver(loop, np.zeros((8, 1)), np.arange(4), plan, time.monotonic,
                 None)
    asyncio.run(drv.run(plan, 0.4))
    rounds = len(drv.sent) / 4
    assert 8 <= rounds <= 21           # ~0.4 s / 0.02 s, never more
    assert [row for _, _, row in drv.sent[:6]] == [0, 1, 2, 3, 0, 1]
    assert loop.images[:5] == [1, 2, 1, 2, 1]


def test_open_loop_sends_on_schedule():
    loop = FakeLoop(0.001)
    offsets = traffic.poisson_offsets(300, 0.3, 11)
    plan = traffic.Plan(sizes=np.ones(len(offsets), int), offsets=offsets)
    drv = Driver(loop, np.zeros((4, 1)), np.arange(4), plan, time.monotonic,
                 None)
    t0 = asyncio.run(drv.run(plan, 0.3))
    dues = np.array([due for _, due, _ in drv.sent])
    assert np.allclose(dues - t0, offsets)
