"""Test data: the parts of `tiny_grouped.json` that the defaults cannot
give, brought as a configuration with grouped convolutions brings them:
kernels of shape (k, k, ci / groups, co), a reference that convolves by
groups, a graph check that also compares each node's groups, and work
counts of grouped multiply-accumulates.  `loss` and `train_flops` are
left to the defaults."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from harness import model, reference, work


def _groups(layer: dict) -> int:
    return layer.get("groups", 1)


def init_params(cfg: dict, seed: int):
    """He normal grouped kernels, N(0, bias_std) biases and a
    1/sqrt(fan_in) head, made on the device in one jitted call."""
    layers = cfg["layers"]
    dtype = jnp.dtype(cfg["dtype"])

    def make(key):
        keys = jax.random.split(key, 2 * len(layers) + 1)
        convs = []
        for i, layer in enumerate(layers):
            shape = (layer["k"], layer["k"], layer["ci"] // _groups(layer),
                     layer["co"])
            fan_in = math.prod(shape[:3])
            w = jax.random.normal(keys[2 * i], shape) * math.sqrt(2 / fan_in)
            b = jax.random.normal(keys[2 * i + 1], (layer["co"],)) \
                * cfg["bias_std"]
            convs.append({"w": w.astype(dtype), "b": b.astype(dtype)})
        co = layers[-1]["co"]
        head = jax.random.normal(keys[-1], (co, cfg["classes"])) \
            / math.sqrt(co)
        return {"convs": convs, "head": head.astype(dtype)}

    return jax.jit(make)(model.prng_key(seed))


def check_graph(cfg: dict, graph):
    """The default row comparison, then each node's groups against its
    row's."""
    dense = [{k: v for k, v in layer.items() if k != "groups"}
             for layer in cfg["layers"]]
    model.check_graph({**cfg, "layers": dense}, graph)
    for i, (node, layer) in enumerate(zip(graph.nodes, cfg["layers"])):
        if node.groups != _groups(layer):
            raise ValueError(f"{cfg['name']}: the program's graph departs "
                             f"from the layer table at row {i} (groups)")
    return graph


def _conv(x, w, stride, pad, groups):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad)] * 2,
        dimension_numbers=reference.DN, feature_group_count=groups,
        precision=reference.HIGHEST, preferred_element_type=x.dtype)


def _matmul(a, b):
    return jnp.dot(a, b, precision=reference.HIGHEST,
                   preferred_element_type=a.dtype)


def logits(cfg: dict, params: dict, images, *, dtype=None, operands=None):
    """images (B, H, W, C) -> logits (B, classes), in float32 out: each
    layer a grouped convolution, bias, ReLU and its max pool; then the
    mean pool and the head, at the precisions of `reference.logits`."""
    dtype = jnp.dtype(dtype or cfg["dtype"])
    operands = operands or (cfg["matmul_operands"] if dtype == jnp.float32
                            else dtype)
    params = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    y = images.astype(dtype)
    for layer, p in zip(cfg["layers"], params["convs"]):
        conv = reference.rounded(functools.partial(
            _conv, stride=layer["stride"], pad=layer["pad"],
            groups=_groups(layer)), operands)
        y = jnp.maximum(conv(y, p["w"]) + p["b"], 0)
        if layer["pool"] > 1:
            win = (1, layer["pool"], layer["pool"], 1)
            y = jax.lax.reduce_window(y, np.array(-np.inf, dtype),
                                      jax.lax.max, win, win, "VALID")
    feats = y.mean(axis=(1, 2))
    return reference.rounded(_matmul, operands)(
        feats, params["head"]).astype(jnp.float32)


def _macs(layer: dict) -> int:
    ho, wo = work.out_plane(layer)
    return ho * wo * layer["k"] ** 2 * (layer["ci"] // _groups(layer)) \
        * layer["co"]


def forward_flops(cfg: dict) -> float:
    return 2.0 * (sum(_macs(layer) for layer in cfg["layers"])
                  + cfg["layers"][-1]["co"] * cfg["classes"])


def pass_work(cfg: dict, batch: int, passes=("fwd",)) -> list[dict]:
    """Per layer and pass: grouped FLOPs and compulsory bytes, as
    `work.pass_work` counts a pass."""
    wb = work.WORD_BYTES[cfg["dtype"]]
    out = []
    for i, layer in enumerate(cfg["layers"]):
        ho, wo = work.out_plane(layer)
        pool = layer["pool"]
        x = batch * layer["h"] * layer["w"] * layer["ci"]
        w = layer["k"] ** 2 * (layer["ci"] // _groups(layer)) * layer["co"]
        y = batch * ho * wo * layer["co"]
        words = {"fwd": x + w + layer["co"]
                 + batch * (ho // pool) * (wo // pool) * layer["co"],
                 "dgrad": y + w + x, "wgrad": x + y + w}
        out += [{"layer": layer["name"], "pass": p,
                 "flops": 2.0 * batch * _macs(layer), "bytes": words[p] * wb}
                for p in passes if not (p == "dgrad" and i == 0)]
    return out
