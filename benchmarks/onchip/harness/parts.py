"""The model-specific parts of a configuration, decided in one place.

A configuration file may name a module of its own under `"model"`: a
path under the benchmark's directory, such as
`"configs/<name>_model.py"`.  The module may define any of

    init_params(cfg, seed)                      weights, on the device
    check_graph(cfg, graph)                     the graph, or raises
    logits(cfg, params, images, *, dtype=None, operands=None)
    loss(cfg, params, images, labels, *, dtype=None)
    forward_flops(cfg)                          FLOPs of one image
    train_flops(cfg)                            FLOPs of one training image
    pass_work(cfg, batch, passes)               [{"layer", "pass",
                                                  "flops", "bytes"}]

with the meanings of the defaults, which serve a dense-conv classifier
from its layer table: `model.init_params`, `model.check_graph`,
`reference.logits`, `reference.loss`, and `work.py`'s counts.  What it
leaves out comes from them; a `loss` left out is the cross-entropy of
the configuration's own `logits`.  `logits` and `loss` are its plain
reference: they import nothing of the program, compute at the precision
the configuration states, and take `dtype` and `operands` as the
controls give them.  `pass_work` returns rows only for the passes it is
asked for, each naming its `pass`.

Nothing else in the harness takes these parts from elsewhere.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable

from harness import model, reference, spec, work


@dataclasses.dataclass(frozen=True)
class Parts:
    init_params: Callable
    check_graph: Callable
    logits: Callable
    loss: Callable
    forward_flops: Callable
    train_flops: Callable
    pass_work: Callable


NAMES = tuple(f.name for f in dataclasses.fields(Parts))


def parts_of(cfg: dict) -> Parts:
    """The configuration's own parts where its module defines them, the
    defaults elsewhere."""
    own = own_module(cfg["model"]) if "model" in cfg else None
    found = {n: getattr(own, n) for n in NAMES if hasattr(own, n)}
    logits = found.get("logits", reference.logits)
    return Parts(**{
        "init_params": model.init_params,
        "check_graph": model.check_graph,
        "logits": logits,
        "loss": functools.partial(reference.loss, logits=logits),
        "forward_flops": work.forward_flops,
        "train_flops": work.train_flops,
        "pass_work": work.pass_work,
        **found})


@functools.cache
def own_module(path: str):
    """The module at `path` under the benchmark's directory, loaded once
    a process."""
    full = (spec.HERE / path).resolve()
    if not full.is_relative_to(spec.HERE) or full.suffix != ".py":
        raise ValueError(f"a configuration's model module is a .py file "
                         f"under {spec.HERE}, not {path!r}")
    return spec._module(full, "onchip_model_")


def program_graph(cfg: dict, params):
    """The program's own graph, built by the builder the configuration
    names under `program`, and checked by the configuration's
    `check_graph` before anything runs."""
    return parts_of(cfg).check_graph(cfg, model.build_graph(cfg, params))
