"""Faults planted under the timed path, to show that the comparison
which decides `correct` catches each fault a cell of its kind can
have.  `--fault <name>` plants one; the benchmark's own runs never do.

* `answer_altered` (serving): one logit of every dispatch moved by 5%
  of the dispatch's largest logit, where the program produces it.
* `half_batch` (serving): only the first half of each bucket computed,
  its logits repeated over the rest.
* `half_batch` (training): the loss taken over the first half of the
  batch alone.
* `unchanged` (training): a step that returns its state unchanged.
"""

from __future__ import annotations

import contextlib

import jax.numpy as jnp


def _answer_altered(graph_logits):
    def broken(*args, **kw):
        z = graph_logits(*args, **kw)
        return z.at[0, 0].add(0.05 * jnp.abs(z).max())
    return broken


def _half_bucket(graph_logits):
    def broken(graph, params, images, **kw):
        half = max(1, images.shape[0] // 2)
        z = graph_logits(graph, params, images[:half], **kw)
        return jnp.resize(z, (images.shape[0], z.shape[1]))
    return broken


def _half_loss(loss):
    def broken(params, batch, target=None):
        n = batch["images"].shape[0] // 2
        return loss(params, {k: v[:n] for k, v in batch.items()}, target)
    return broken


def _unchanged(sgd_step):
    def make(loss_fn, hyper, dtype):
        step = sgd_step(loss_fn, hyper, dtype)

        def broken(state, ring):
            _, loss = step(state, ring)
            return state, loss
        return broken
    return make


@contextlib.contextmanager
def planted(name: str | None, kind: str):
    """Within the block, the program (or the step) has fault `name`."""
    if name is None:
        yield
        return
    import repro.models.cnn as cnn
    import repro.serve.server as server
    from harness import train_cell

    where = {("serve", "answer_altered"): (server, "graph_logits",
                                           _answer_altered),
             ("serve", "half_batch"): (server, "graph_logits", _half_bucket),
             ("train", "half_batch"): (cnn, "vgg_loss", _half_loss),
             ("train", "unchanged"): (train_cell, "sgd_step", _unchanged)}
    mod, attr, fault = where[(kind, name)]
    sound = getattr(mod, attr)
    setattr(mod, attr, fault(sound))
    try:
        yield
    finally:
        setattr(mod, attr, sound)
