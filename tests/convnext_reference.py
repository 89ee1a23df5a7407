"""Plain float32 reference of ConvNeXt's forward pass (Liu et al.,
arXiv:2201.03545, Sec. 2; the published ``models/convnext.py``), in
straightforward ``jax.numpy`` under ``jax.default_matmul_precision
("highest")``: no kernels, no planner, no batching.

    stem:    x = LN(conv4x4/s4(x) + b)
    stage i: (i > 1) x = conv2x2/s2(LN(x)) + b
             block:  y = LN(dwconv7x7(x) + b)
                     y = GELU(y @ W1 + b1)          (exact, erf)
                     x = x + gamma * (y @ W2 + b2)
    head:    logits = LN(mean_hw(x)) @ W + b

Every LayerNorm is over the channels, eps 1e-6, biased variance.  Drop
path is the identity at inference.  Departures from the published
module: none in the arithmetic.  The weights are read from the
program's parameter layout (``repro.models.cnn.convnext_graph``'s
docstring), in which the LayerNorm before a downsampling conv is stored
on the last block of the stage before it; here it is applied where the
paper applies it, as the first step of the downsampling layer.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

EPS = 1e-6
DN = ("NHWC", "HWIO", "NHWC")


def layer_norm(x, w, b):
    mean = x.mean(axis=-1, keepdims=True)
    var = ((x - mean) ** 2).mean(axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + EPS) * w + b


def conv(x, w, *, stride=1, pad=0, groups=1):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad)] * 2, dimension_numbers=DN,
        feature_group_count=groups)


def logits(params, images, depths=(3, 3, 9, 3), *, skip_norm=None):
    """images (B, H, W, 3) -> (B, classes).  ``skip_norm`` names a block
    (``"s{i}b{j}"``) whose LayerNorm after the depthwise conv is left
    out: a reference that should no longer agree."""
    with jax.default_matmul_precision("highest"):
        convs = iter(params["convs"])
        p = next(convs)                                   # stem
        x = conv(images.astype(jnp.float32), p["w"], stride=4) + p["b"]
        x = layer_norm(x, p["norm_w"], p["norm_b"])
        pre_norm = None
        for si, depth in enumerate(depths, start=1):
            if si > 1:                                    # downsampling
                p = next(convs)
                x = conv(layer_norm(x, *pre_norm), p["w"], stride=2) \
                    + p["b"]
            for bi in range(depth):
                dw, pw1, pw2 = next(convs), next(convs), next(convs)
                c = x.shape[-1]
                y = conv(x, dw["w"], pad=3, groups=c) + dw["b"]
                if skip_norm != f"s{si}b{bi}":
                    y = layer_norm(y, dw["norm_w"], dw["norm_b"])
                y = jax.nn.gelu(y @ pw1["w"][0, 0] + pw1["b"],
                                approximate=False)
                y = y @ pw2["w"][0, 0] + pw2["b"]
                x = x + pw2["scale"] * y
            if si < len(depths):
                pre_norm = (pw2["norm_w"], pw2["norm_b"])
        feats = layer_norm(x.mean(axis=(1, 2)), params["head_norm_w"],
                           params["head_norm_b"])
        return feats @ params["head"] + params["head_b"]
