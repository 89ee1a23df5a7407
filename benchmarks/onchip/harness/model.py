"""Weights from the seed, and the program's graph checked against the
configuration's layer table: with `reference.py` and `work.py`, the
default parts of a dense-conv classifier (`harness/parts.py`)."""

from __future__ import annotations

import importlib
import math

import jax
import jax.numpy as jnp


def prng_key(seed: int):
    """A JAX key from any whole number below 2**64."""
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed & 0xFFFFFFFF)


def init_params(cfg: dict, seed: int):
    """`{"convs": [{"w", "b"}], "head"}` made on the device in one jitted
    call: He normal weights (1/sqrt(fan_in) where no ReLU follows),
    biases from N(0, bias_std), a 1/sqrt(fan_in) head."""
    layers, classes = cfg["layers"], cfg["classes"]
    dtype = jnp.dtype(cfg["dtype"])

    def make(key):
        keys = jax.random.split(key, 2 * len(layers) + 1)
        convs = []
        for i, layer in enumerate(layers):
            fan_in = layer["k"] ** 2 * layer["ci"]
            gain = math.sqrt(2.0) if layer.get("relu", True) else 1.0
            shape = (layer["k"], layer["k"], layer["ci"], layer["co"])
            w = jax.random.normal(keys[2 * i], shape) * gain / math.sqrt(
                fan_in)
            b = jax.random.normal(keys[2 * i + 1], (layer["co"],)) \
                * cfg["bias_std"]
            convs.append({"w": w.astype(dtype), "b": b.astype(dtype)})
        co = layers[-1]["co"]
        head = jax.random.normal(keys[-1], (co, classes)) / math.sqrt(co)
        return {"convs": convs, "head": head.astype(dtype)}

    return jax.jit(make)(prng_key(seed))


def resolve(path: str):
    """`"package.module:name"` -> the object."""
    mod, name = path.split(":")
    return getattr(importlib.import_module(mod), name)


def build_graph(cfg: dict, params):
    """The program's own ConvGraph for this configuration, built by the
    builder the configuration names under `program`."""
    prog = cfg["program"]
    build = resolve(prog["graph"])
    return build(params) if prog["graph_args"] == "params" else build()


def check_graph(cfg: dict, graph):
    """`graph`, checked layer by layer against the configuration's table
    (geometry, effective pool, edges) before anything runs; raises where
    it departs."""
    from repro.models.graph import graph_stages

    h, w, c = cfg["image"]
    stages = graph_stages(graph, h, w, c)
    got = [{"name": st.node.name, "ci": st.node.ci, "co": st.node.co,
            "k": st.node.hk, "stride": st.node.stride, "pad": st.node.pad,
            "h": st.h, "w": st.w, "pool": st.pool,
            "relu": st.node.relu, "src": st.node.src,
            "residual": st.node.residual} for st in stages]
    want = [{"relu": True, "src": None, "residual": None, **layer}
            for layer in cfg["layers"]]
    if got != want:
        bad = next(i for i, (g, t) in enumerate(zip(got, want)) if g != t) \
            if len(got) == len(want) else len(got)
        raise ValueError(f"{cfg['name']}: the program's graph departs from "
                         f"the layer table at row {bad}")
    return graph


def fallbacks() -> int:
    """The program's process-wide tally of conv passes that left the
    kernel for lax; a run counts from its own start."""
    from repro.kernels.conv_lb.ops import exec_fallback_counts

    return sum(exec_fallback_counts().values())
