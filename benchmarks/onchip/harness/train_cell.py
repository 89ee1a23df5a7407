"""A training cell: the program's loss under `jax.value_and_grad`, with
SGD, momentum and weight decay, in one jitted step over a ring of
seeded batches resident on the device.

Set-up builds the step and its state once, and drives that same object
through its first `checked_steps` steps, recording each loss, the
first gradient (from the momentum after step 1) and the change of the
parameters after the last of them; the window then keeps stepping the
same state.  The reference follows those first steps from the same
weights and batches, and the gaps between the two are compared.
"""

from __future__ import annotations

import collections
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from harness import model
from harness.parts import parts_of, program_graph
from harness.readers import TracedWindow

IN_FLIGHT = 2           # steps the host may enqueue ahead of the device
TRACE_S = 2.0           # the traced steps last about this long (at most
                        # half a shorter window)


def sgd_step(loss_fn, hyper, dtype):
    """One step of (params, momentum, k, ring) -> (state, loss): batch k
    of the ring, SGD with momentum and weight decay."""
    lr, mu, wd = hyper["lr"], hyper["momentum"], hyper["weight_decay"]

    def step(state, ring):
        params, mom, k = state
        images, labels = ring
        batch = {"images": images[k % images.shape[0]],
                 "labels": labels[k % labels.shape[0]]}
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        mom = jax.tree_util.tree_map(
            lambda m, g, p: (mu * m + g + wd * p).astype(dtype),
            mom, grads, params)
        params = jax.tree_util.tree_map(
            lambda p, m: (p - lr * m).astype(dtype), params, mom)
        return (params, mom, k + 1), loss

    return step


def leaf_norms(tree) -> np.ndarray:
    return np.asarray([float(jnp.linalg.norm(x.astype(jnp.float32)))
                       for x in jax.tree_util.tree_leaves(tree)])


def norm_gap(got: np.ndarray, ref: np.ndarray, keep=None) -> float:
    """Worst leaf's gap between the program's norm and the reference's,
    over the reference's norm of that leaf or of the median leaf,
    whichever is larger."""
    keep = np.ones(len(ref), bool) if keep is None else keep
    scale = np.maximum(ref, np.median(ref[keep]))
    return float((np.abs(got - ref) / scale)[keep].max())


class TrainWindow:
    def __init__(self, cell, seed: int, *, target, control: bool = False):
        self.cell, self.seed, self.target = cell, seed, target
        self.control = control
        self.parts = parts_of(cell.cfg)

    def _loss(self):
        cfg = self.cell.cfg
        if self.control:
            return lambda p, b: self.parts.loss(
                cfg, p, b["images"], b["labels"], dtype="bfloat16")
        loss = model.resolve(cfg["program"]["train_loss"])
        return lambda p, b: loss(p, b, self.target)

    def start(self) -> None:
        self.fallbacks0 = model.fallbacks()
        cfg, tr = self.cell.cfg, self.cell.traffic
        dtype = jnp.bfloat16 if self.control else jnp.dtype(cfg["dtype"])
        self.params0 = self.parts.init_params(cfg, self.seed)
        program_graph(cfg, self.params0)
        images, labels = self.cell.kind.ring(tr, cfg, self.seed)
        self.host_ring = (images, labels)
        self.ring = jax.device_put((images, labels))
        start = jax.tree_util.tree_map(lambda a: a.astype(dtype),
                                       self.params0)
        state = (start, jax.tree_util.tree_map(jnp.zeros_like, start),
                 jnp.zeros((), jnp.int32))
        self.step = jax.jit(sgd_step(self._loss(), tr, dtype))
        self.losses = []
        for k in range(tr["checked_steps"]):
            state, loss = self.step(state, self.ring)
            self.losses.append(float(loss))
            if k == 0:
                self.grad1 = leaf_norms(jax.tree_util.tree_map(
                    lambda m, p: m.astype(jnp.float32)
                    - tr["weight_decay"] * p.astype(jnp.float32),
                    state[1], start))
        self.change = leaf_norms(jax.tree_util.tree_map(
            lambda p, q: p.astype(jnp.float32) - q.astype(jnp.float32),
            state[0], start))
        self.state = jax.block_until_ready(state)

    def _run(self, steps=None, until=None) -> int:
        pending = collections.deque()
        n = 0
        while (steps is not None and n < steps) or (
                until is not None and time.monotonic() < until):
            with jax.profiler.TraceAnnotation("onchip.step"):
                self.state, loss = self.step(self.state, self.ring)
            pending.append(loss)
            n += 1
            if len(pending) > IN_FLIGHT:
                pending.popleft().block_until_ready()
        jax.block_until_ready(self.state)
        return n

    def measure(self, seconds: float, trace_window: bool) -> dict:
        from harness import trace

        tr = self.cell.traffic
        t0 = time.monotonic()
        if trace_window:
            warm = self._run(steps=2)
            per_step = (time.monotonic() - t0) / warm
            traced = max(2, round(min(TRACE_S, seconds / 2) / per_step))
            cap = trace.Capture()
            cap.start()
            cap.begin()
            self._run(steps=traced)
            cap.end()
            steps = warm + traced + self._run(until=t0 + seconds)
            cap.stop()
        else:
            steps = self._run(until=t0 + seconds)
        elapsed = time.monotonic() - t0
        out = {"attempted": steps, "failed": 0,
               "values": {"train_img_per_s": tr["batch"] * steps / elapsed},
               "notes": {"steps": steps, "window_s": elapsed}}
        if trace_window:
            out["traced"] = TracedWindow(
                cfg=self.cell.cfg, peak={},
                summary=cap.summary(), passes=("fwd", "wgrad", "dgrad"),
                batches=[tr["batch"]] * traced,
                real_images=tr["batch"] * traced,
                flops_per_image=self.parts.train_flops(self.cell.cfg))
        return out

    def health(self) -> dict:
        return {"fallbacks": model.fallbacks() - self.fallbacks0}

    def release(self) -> None:
        del self.state, self.step, self.ring

    def compare(self, block: int = 16) -> dict:
        """The reference follows the checked steps from the same weights
        and batches, `block` rows at a time."""
        cfg, tr = self.cell.cfg, self.cell.traffic
        images, labels = self.host_ring
        batch = tr["batch"]
        grad_fn = jax.jit(jax.value_and_grad(
            functools.partial(self.parts.loss, cfg), argnums=0))
        params = self.params0
        mom = jax.tree_util.tree_map(jnp.zeros_like, params)
        losses = []
        for k in range(tr["checked_steps"]):
            r = k % len(images)
            loss, grads = 0.0, None
            for i in range(0, batch, block):
                part_loss, part = grad_fn(
                    params, jnp.asarray(images[r, i:i + block]),
                    jnp.asarray(labels[r, i:i + block]))
                share = len(images[r, i:i + block]) / batch
                loss += float(part_loss) * share
                part = jax.tree_util.tree_map(lambda g: g * share, part)
                grads = part if grads is None else jax.tree_util.tree_map(
                    jnp.add, grads, part)
            losses.append(loss)
            if k == 0:
                ref_grad1 = leaf_norms(grads)
            mom = jax.tree_util.tree_map(
                lambda m, g, p: tr["momentum"] * m + g
                + tr["weight_decay"] * p, mom, grads, params)
            params = jax.tree_util.tree_map(
                lambda p, m: p - tr["lr"] * m, params, mom)
        ref_change = leaf_norms(jax.tree_util.tree_map(
            jnp.subtract, params, self.params0))
        # leaves the reference's gradient leaves unmoved (a bias under
        # softmax) move by round-off alone: not compared
        moved = ref_grad1 >= 1e-3 * np.median(ref_grad1)
        loss_gap = max(abs(a - b) / abs(b) for a, b in zip(self.losses,
                                                           losses))
        return {"loss_gap": float(loss_gap),
                "grad_gap": norm_gap(self.grad1, ref_grad1),
                "change_gap": norm_gap(self.change, ref_change, moved)}
