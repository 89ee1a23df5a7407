"""What every traffic mix draws on.  A mix is a data file of parameters,
`traffic/<mix>.json`; its `kind` names the module `traffic/<kind>.py`
that turns the parameters into load, so a new shape of load is a new
module and a new mix a new data file, with no edit to a file that is
there.  A kind module says which cell drives it (`CELL = "serve"` or
`"train"`) and gives, for a serving cell, `plan(mix, seconds, seed)`:
the `Plan` of the window's requests; for a training cell,
`ring(mix, cfg, seed)`: the batches of the ring.

Every seed gets the same set of request sizes and gaps, in an order of
its own: the seed changes which request comes when, not how much work
arrives.  Images come from a pool of `pool_images` seeded images, made
on the host as a network would deliver them; a request of n images
takes n consecutive images of the pool.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass
class Plan:
    """The requests of one window.  Open loop: each request's send time
    in seconds from the window's start (`offsets`).  Closed loop
    (`offsets` None): `clients` requests kept outstanding, each
    completion sending the next.  `sizes[i]` is the number of images of
    the i-th request sent (cycled where a closed loop sends more)."""
    sizes: np.ndarray
    offsets: np.ndarray | None = None
    clients: int = 0


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([stream, seed])


def image_pool(traffic: dict, cfg: dict, seed: int) -> np.ndarray:
    h, w, c = cfg["image"]
    return rng(seed, 1).standard_normal(
        (traffic["pool_images"], h, w, c), dtype=np.float32)


def poisson_gaps(rate: float, seconds: float, seed: int) -> np.ndarray:
    """rate x seconds exponential gaps by their quantiles, scaled to
    fill the window, in a seeded order."""
    n = max(1, round(rate * seconds))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps *= seconds / gaps.sum()
    return rng(seed, 2).permutation(gaps)


def poisson_offsets(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Send times in [0, seconds), the first at 0."""
    gaps = poisson_gaps(rate, seconds, seed)
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def request_sizes(traffic: dict, n: int, seed: int) -> np.ndarray:
    """Images of each of `n` requests: the mix's `images_per_request`,
    `[[images, weight], ...]` (one image each where it has none), in
    counts proportional to the weights, in a seeded order."""
    table = traffic.get("images_per_request", [[1, 1]])
    sizes = np.asarray([s for s, _ in table], dtype=np.int64)
    w = np.asarray([x for _, x in table], dtype=np.float64)
    exact = n * w / w.sum()
    counts = np.floor(exact).astype(np.int64)
    rest = np.argsort(counts - exact, kind="stable")[:n - counts.sum()]
    counts[rest] += 1
    return rng(seed, 5).permutation(np.repeat(sizes, counts))


def request_rows(traffic: dict, n: int, largest: int,
                 seed: int) -> np.ndarray:
    """First pool index of each of `n` requests of up to `largest`
    images."""
    return rng(seed, 3).integers(0, traffic["pool_images"] - largest + 1, n)


def train_ring(traffic: dict, cfg: dict, seed: int):
    """(images, labels) of `ring_batches` batches, all rows distinct."""
    h, w, c = cfg["image"]
    n = traffic["ring_batches"] * traffic["batch"]
    g = rng(seed, 4)
    images = g.standard_normal((n, h, w, c), dtype=np.float32)
    labels = g.integers(0, cfg["classes"], n).astype(np.int32)
    shape = (traffic["ring_batches"], traffic["batch"])
    return images.reshape(*shape, h, w, c), labels.reshape(shape)


def percentile(latencies_s, q: float) -> float:
    """Nearest-rank q-th percentile; a request with no latency (shed,
    failed, never answered) is a miss and counts as +inf."""
    lat = sorted(math.inf if v is None else v for v in latencies_s)
    if not lat:
        return math.inf
    return lat[max(0, math.ceil(q / 100 * len(lat)) - 1)]
