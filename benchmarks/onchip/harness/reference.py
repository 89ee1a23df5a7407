"""The plain reference of a conv classifier, built from the layer table
of its configuration file and nothing of the program.

Each layer: convolution, bias, the residual join, ReLU, the max pool
the table states; then the global mean pool and the linear head.  The
configuration states its precision: operands of every product rounded
to `matmul_operands` (bfloat16 is one MXU pass, what a float32 matmul
at default precision does on a TPU) with the products accumulated in
float32, and float32 weights and training state.  The reference keeps
everything else in float32, rounds the operands itself and computes
the products at HIGHEST precision, so it means the same on any
backend; its gradients round the operands of the backward products
the same way.

The controls, one step below the stated precision: for training,
whose state the configuration keeps in float32, everything in bfloat16
(`dtype="bfloat16"`); for serving, products of float8 operands
(`operands="float8_e4m3fn"`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
DN = ("NHWC", "HWIO", "NHWC")


def _round(x, operand_dtype):
    operand_dtype = jnp.dtype(operand_dtype)
    if operand_dtype == x.dtype:
        return x
    if operand_dtype.itemsize == 1:
        # float8 with a per-tensor power-of-two scale, as fp8 matmuls
        # are run: the largest magnitude lands in the format's range
        top = jnp.max(jnp.abs(x)) / float(jnp.finfo(operand_dtype).max)
        scale = 2.0 ** jnp.ceil(jnp.log2(jnp.maximum(top, 1e-30)))
        return (x / scale).astype(operand_dtype).astype(x.dtype) * scale
    return x.astype(operand_dtype).astype(x.dtype)


def _conv(x, w, stride, pad):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad)] * 2, dimension_numbers=DN,
        precision=HIGHEST, preferred_element_type=x.dtype)


def _matmul(a, b):
    return jnp.dot(a, b, precision=HIGHEST, preferred_element_type=a.dtype)


def rounded(op, operand_dtype):
    """`op(a, b)` with both operands rounded to `operand_dtype`, and the
    operands of its two backward products rounded too."""

    @jax.custom_vjp
    def f(a, b):
        return op(_round(a, operand_dtype), _round(b, operand_dtype))

    def fwd(a, b):
        ar, br = _round(a, operand_dtype), _round(b, operand_dtype)
        return op(ar, br), (ar, br)

    def bwd(res, g):
        _, vjp = jax.vjp(op, *res)
        return vjp(_round(g, operand_dtype))

    f.defvjp(fwd, bwd)
    return f


def logits(cfg: dict, params: dict, images, *, dtype=None, operands=None):
    """images (B, H, W, C) -> logits (B, classes), in float32 out.
    `dtype` and `operands` override the configuration's storage and
    product-operand precisions."""
    dtype = jnp.dtype(dtype or cfg["dtype"])
    operands = operands or (cfg["matmul_operands"] if dtype == jnp.float32
                            else dtype)
    cast = functools.partial(jax.tree_util.tree_map,
                             lambda a: a.astype(dtype))
    params = cast(params)
    tensors = {"input": images.astype(dtype)}
    prev = "input"
    for layer, p in zip(cfg["layers"], params["convs"]):
        conv = rounded(functools.partial(_conv, stride=layer["stride"],
                                         pad=layer["pad"]), operands)
        y = conv(tensors[layer.get("src") or prev], p["w"])
        y = y + p["b"]
        if layer.get("residual"):
            y = y + tensors[layer["residual"]]
        if layer.get("relu", True):
            y = jnp.maximum(y, 0)
        pool = layer.get("pool", 1)
        if pool > 1:
            win = (1, pool, pool, 1)
            y = jax.lax.reduce_window(y, np.array(-np.inf, dtype),
                                      jax.lax.max, win, win, "VALID")
        tensors[layer["name"]] = y
        prev = layer["name"]
    feats = tensors[prev].mean(axis=(1, 2)).astype(dtype)
    return rounded(_matmul, operands)(feats, params["head"]).astype(
        jnp.float32)


def loss(cfg: dict, params: dict, images, labels, *, dtype=None,
         logits=logits):
    """Mean softmax cross-entropy of the reference logits (of `logits`,
    a configuration's own reference where it brings one)."""
    z = logits(cfg, params, images, dtype=dtype)
    logp = jax.nn.log_softmax(z)
    return -jnp.take_along_axis(logp, labels[:, None], axis=1).mean()


def logits_in_blocks(cfg: dict, params: dict, images, *, block: int,
                     dtype=None, logits=logits):
    """Reference logits (of `logits`, as for `loss`) of host or device
    images, `block` rows at a time, so that a large batch fits next to
    nothing else."""
    fn = jax.jit(functools.partial(logits, cfg, dtype=dtype))
    outs = [fn(params, jnp.asarray(images[i:i + block]))
            for i in range(0, len(images), block)]
    return jnp.concatenate(outs)
