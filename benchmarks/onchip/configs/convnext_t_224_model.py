"""The parts of a ConvNeXt configuration (`convnext_t_224.json`) that the
dense-conv defaults cannot give, built from its layer table and nothing
of the program.

A row of the table is one conv node: `ci`, `co`, `k`, `stride`, `pad`,
input plane `h`, `w`, `groups` (1 where absent; `groups == ci == co` is
a depthwise conv), and its epilogue, in order: bias, `gelu` (exact,
erf), `scale` (a per-channel layer scale), the `residual` join, `norm`
(LayerNorm over the channels, eps `norm_eps`).  After the last row: the
mean pool, the head's LayerNorm, the linear head and its bias.  The
LayerNorm the paper puts before each downsampling conv is the `norm` of
the row before it, whose output nothing else reads, so the two are the
same computation.

Weights (`init_params`), in the layout the program's parameter pytree
has: `{"convs": [{"w", "b"[, "scale"][, "norm_w", "norm_b"]}],
"head", "head_b", "head_norm_w", "head_norm_b"}`, a kernel of shape
(k, k, ci / groups, co).  Drawn as the configuration's `assumed` says.

Precision (`logits`): the operands of every dense product (the convs
with groups 1 and the head) rounded to `matmul_operands`, those of the
depthwise products to `depthwise_operands`, products accumulated in
float32; norms, GELU and the rest in float32.  `operands` puts one
precision on every product: the float8 control.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from harness import model, reference, work

# FLOPs an output element of each epilogue step, counted with no bytes
# of their own: a program that runs a step as a pass of its own then
# reads worse than one that fuses it
EPILOGUE_FLOPS = {"bias": 1, "gelu": 8, "scale": 1, "residual": 1,
                  "norm": 8}


def _groups(layer: dict) -> int:
    return layer.get("groups", 1)


def _depthwise(layer: dict) -> bool:
    return 1 < _groups(layer) == layer["ci"] == layer["co"]


def init_params(cfg: dict, seed: int):
    """Weights from the seed, made on the device in one jitted call."""
    layers, classes = cfg["layers"], cfg["classes"]
    a = cfg["assumed"]
    dtype = jnp.dtype(cfg["dtype"])

    def make(key):
        keys = iter(jax.random.split(key, 5 * len(layers) + 4))
        convs = []
        for layer in layers:
            shape = (layer["k"], layer["k"], layer["ci"] // _groups(layer),
                     layer["co"])
            gain = math.sqrt(2.0) if layer.get("gelu") else 1.0
            p = {"w": jax.random.normal(next(keys), shape) * gain
                 / math.sqrt(math.prod(shape[:3])),
                 "b": jax.random.normal(next(keys), (layer["co"],))
                 * a["bias_std"]}
            if layer.get("scale"):
                lo, hi = a["layer_scale_uniform"]
                p["scale"] = jax.random.uniform(next(keys), (layer["co"],),
                                                minval=lo, maxval=hi)
            if layer.get("norm"):
                p["norm_w"] = 1.0 + a["norm_w_std"] * jax.random.normal(
                    next(keys), (layer["co"],))
                p["norm_b"] = a["norm_b_std"] * jax.random.normal(
                    next(keys), (layer["co"],))
            convs.append(p)
        co = layers[-1]["co"]
        params = {
            "convs": convs,
            "head": jax.random.normal(next(keys), (co, classes))
            / math.sqrt(co),
            "head_b": jax.random.normal(next(keys), (classes,))
            * a["bias_std"],
            "head_norm_w": 1.0 + a["norm_w_std"] * jax.random.normal(
                next(keys), (co,)),
            "head_norm_b": a["norm_b_std"] * jax.random.normal(
                next(keys), (co,))}
        return jax.tree_util.tree_map(lambda x: x.astype(dtype), params)

    return jax.jit(make)(model.prng_key(seed))


def check_graph(cfg: dict, graph):
    """`graph`, checked row by row against the layer table: geometry,
    groups, epilogue and edges, and the head's norm and bias; raises
    where it departs."""
    from repro.models.graph import graph_stages

    h, w, c = cfg["image"]
    got = [{"name": st.node.name, "ci": st.node.ci, "co": st.node.co,
            "k": st.node.hk, "stride": st.node.stride, "pad": st.node.pad,
            "h": st.h, "w": st.w, "pool": st.pool, "groups": st.node.groups,
            "relu": st.node.relu, "gelu": st.node.gelu,
            "scale": st.node.scale, "norm": st.node.norm,
            "src": st.node.src, "residual": st.node.residual}
           for st in graph_stages(graph, h, w, c)]
    want = [{"pool": 1, "groups": 1, "relu": False, "gelu": False,
             "scale": False, "norm": False, "src": None, "residual": None,
             **layer} for layer in cfg["layers"]]
    if got != want:
        bad = next(i for i, (g, t) in enumerate(zip(got, want)) if g != t) \
            if len(got) == len(want) else min(len(got), len(want))
        raise ValueError(f"{cfg['name']}: the program's graph departs from "
                         f"the layer table at row {bad}")
    if not graph.head_norm:
        raise ValueError(f"{cfg['name']}: the program's head has no "
                         f"LayerNorm and no bias")
    return graph


def _conv(x, w, stride, pad, groups):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad)] * 2,
        dimension_numbers=reference.DN, feature_group_count=groups,
        precision=reference.HIGHEST, preferred_element_type=x.dtype)


def _matmul(a, b):
    return jnp.dot(a, b, precision=reference.HIGHEST,
                   preferred_element_type=a.dtype)


def _layer_norm(x, w, b, eps):
    mean = x.mean(axis=-1, keepdims=True)
    var = ((x - mean) ** 2).mean(axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


def logits(cfg: dict, params: dict, images, *, dtype=None, operands=None):
    """images (B, H, W, C) -> logits (B, classes), in float32 out.
    `dtype` overrides the storage precision; `operands` the operand
    precision of every product, dense and depthwise alike."""
    dtype = jnp.dtype(dtype or cfg["dtype"])
    f32 = dtype == jnp.float32
    dense_ops = operands or (cfg["matmul_operands"] if f32 else dtype)
    dw_ops = operands or (cfg["depthwise_operands"] if f32 else dtype)
    eps = cfg["norm_eps"]
    params = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    tensors = {"input": images.astype(dtype)}
    prev = "input"
    for layer, p in zip(cfg["layers"], params["convs"]):
        conv = reference.rounded(functools.partial(
            _conv, stride=layer["stride"], pad=layer["pad"],
            groups=_groups(layer)),
            dw_ops if _depthwise(layer) else dense_ops)
        y = conv(tensors[layer.get("src") or prev], p["w"]) + p["b"]
        if layer.get("gelu"):
            y = jax.nn.gelu(y, approximate=False)
        if layer.get("scale"):
            y = y * p["scale"]
        if layer.get("residual"):
            y = y + tensors[layer["residual"]]
        if layer.get("norm"):
            y = _layer_norm(y, p["norm_w"], p["norm_b"], eps)
        tensors[layer["name"]] = y
        prev = layer["name"]
    feats = _layer_norm(tensors[prev].mean(axis=(1, 2)),
                        params["head_norm_w"], params["head_norm_b"], eps)
    z = reference.rounded(_matmul, dense_ops)(feats, params["head"])
    return (z + params["head_b"]).astype(jnp.float32)


def _macs(layer: dict) -> int:
    ho, wo = work.out_plane(layer)
    return ho * wo * layer["k"] ** 2 * (layer["ci"] // _groups(layer)) \
        * layer["co"]


def _epilogue_flops(layer: dict) -> int:
    return sum(n for step, n in EPILOGUE_FLOPS.items()
               if step == "bias" or layer.get(step))


def forward_flops(cfg: dict) -> float:
    """Multiply-add FLOPs of one image: every conv and the head."""
    return 2.0 * (sum(_macs(layer) for layer in cfg["layers"])
                  + cfg["layers"][-1]["co"] * cfg["classes"])


def pass_work(cfg: dict, batch: int, passes=("fwd",)) -> list[dict]:
    """Forward FLOPs and compulsory HBM bytes at `batch`, a row a conv
    node and one for the head.  A conv reads its input, kernel, bias and
    epilogue rows (and the residual) and writes its output once; its
    epilogue adds FLOPs and no bytes.  The head reads the last feature
    map, its norm, weights and bias, and writes the logits.  Serving
    only: no other pass has rows."""
    if "fwd" not in passes:
        return []
    wb = work.WORD_BYTES[cfg["dtype"]]
    out = []
    for layer in cfg["layers"]:
        ho, wo = work.out_plane(layer)
        co = layer["co"]
        y = batch * ho * wo * co
        rows = co * (1 + bool(layer.get("scale"))
                     + 2 * bool(layer.get("norm")))
        words = (batch * layer["h"] * layer["w"] * layer["ci"]
                 + layer["k"] ** 2 * (layer["ci"] // _groups(layer)) * co
                 + rows + (y if layer.get("residual") else 0) + y)
        out.append({"layer": layer["name"], "pass": "fwd",
                    "flops": 2.0 * batch * _macs(layer)
                    + y * _epilogue_flops(layer),
                    "bytes": words * wb})
    last = cfg["layers"][-1]
    ho, wo = work.out_plane(last)
    c, k = last["co"], cfg["classes"]
    out.append({"layer": "head", "pass": "fwd",
                "flops": batch * (ho * wo * c + c * EPILOGUE_FLOPS["norm"]
                                  + 2 * c * k + k),
                "bytes": wb * (batch * ho * wo * c + 2 * c + c * k + k
                               + batch * k)})
    return out
