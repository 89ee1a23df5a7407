"""Mosaic compiles the main path's conv kernels for a v5e chip.

Ahead-of-time compiles (no chip attached: the TPU compiler targets a
described ``v5e:2x2`` topology) of the forward kernel and of the
backward's dgrad + wgrad kernels at real widths, on the plans a
``target="compiled"`` run executes:

  * VGG16/224 conv1_1 (the Ci=3 contraction), conv3_3 (256 channels,
    fused 2x2 pool) and conv5_1 (512 channels, a 14-wide plane padded
    to the 16-row sublane);
  * ResNet-20/32 s2b0_a (3x3 stride 2: strided window loads, and the
    lhs-dilated dgrad) and s2b0_proj (the 1x1 stride-2 projection).

and ConvNeXt-T/224's new kernels: the ``conv_dw`` depthwise 7x7 kernel
at each stage's width (96 and 192 are not LANE multiples) with its
fused LayerNorm, the 1x1 convs' GELU and layer-scale + residual +
LayerNorm epilogues, and the 4x4 stride-4 patch stem.

Each compile must contain the Pallas kernels (``tpu_custom_call``) and
record no ``exec.fallback``: what interpret mode accepts but Mosaic
refuses (unaligned tiles, strided value slices, interior padding,
more VMEM than the kernel asks for) fails here, not on the chip.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.exec_target import COMPILED
from repro.kernels.conv_lb.ops import (conv2d_lb, exec_fallback_counts,
                                       reset_fallback_counts)

# (name, batch, plane, ci, co, kernel, stride, pad, pool)
LAYERS = {
    "vgg_conv1_1": (8, 224, 3, 64, 3, 1, 1, 1),
    "vgg_conv3_3": (8, 56, 256, 256, 3, 1, 1, 2),
    "vgg_conv5_1": (8, 14, 512, 512, 3, 1, 1, 1),
    "resnet_s2b0_a": (8, 32, 16, 32, 3, 2, 1, 1),
    "resnet_s2b0_proj": (8, 32, 16, 32, 1, 2, 0, 1),
}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache; keep it out of the way while these run
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    reset_fallback_counts()
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert not exec_fallback_counts(), exec_fallback_counts()
    return hlo.count("tpu_custom_call")


@pytest.mark.parametrize("layer", sorted(LAYERS))
def test_forward_kernel_compiles(one_chip, layer):
    b, hw, ci, co, k, stride, pad, pool = LAYERS[layer]

    def fwd(x, w, bias):
        return conv2d_lb(x, w, bias, stride=stride, padding=pad,
                         relu=True, pool=pool, target=COMPILED)

    assert _compile(fwd, one_chip, (b, hw, hw, ci), (k, k, ci, co),
                    (co,)) == 1


@pytest.mark.parametrize("layer", ["resnet_s2b0_a", "resnet_s2b0_proj",
                                   "vgg_conv3_3"])
def test_backward_kernels_compile(one_chip, layer):
    """dgrad (lhs-dilated for the strided layers) + wgrad: two kernels
    — the VJP never re-runs the forward kernel."""
    b, hw, ci, co, k, stride, pad, pool = LAYERS[layer]

    def grads(x, w):
        return jax.grad(lambda x, w: conv2d_lb(
            x, w, stride=stride, padding=pad, relu=True, pool=pool,
            target=COMPILED).sum(), argnums=(0, 1))(x, w)

    assert _compile(grads, one_chip, (b, hw, hw, ci), (k, k, ci, co)) == 2


def test_kernels_carry_their_pass_names(one_chip):
    """Each Pallas call is named by its pass, so a device profile tells
    conv_fwd, conv_dgrad and conv_wgrad apart (the HLO instruction and
    its op_name), while the custom-call target a reader matches stays
    tpu_custom_call."""
    b, hw, ci, co, k, stride, pad, pool = LAYERS["vgg_conv3_3"]
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in ((b, hw, hw, ci), (k, k, ci, co))]

    def fwd(x, w):
        return conv2d_lb(x, w, padding=pad, relu=True, pool=pool,
                         target=COMPILED)

    def grads(x, w):
        return jax.grad(lambda x, w: fwd(x, w).sum(),
                        argnums=(0, 1))(x, w)

    fwd_hlo = jax.jit(fwd).lower(*args).compile().as_text()
    bwd_hlo = jax.jit(grads).lower(*args).compile().as_text()
    calls = [line for line in (fwd_hlo + bwd_hlo).splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    names = sorted(line.split(" = ")[0].split()[-1].lstrip("%")
                   .split(".")[0] for line in calls)
    assert names == ["conv_dgrad", "conv_fwd", "conv_wgrad"]
    assert "/conv_fwd/pallas_call" in fwd_hlo


# (name: batch, plane, channels) of ConvNeXt-T's depthwise layers
DEPTHWISE = {"convnext_s1_dw": (8, 56, 96), "convnext_s2_dw": (8, 28, 192),
             "convnext_s3_dw": (8, 14, 384), "convnext_s4_dw": (8, 7, 768)}


@pytest.mark.parametrize("layer", sorted(DEPTHWISE))
def test_depthwise_kernel_compiles(one_chip, layer):
    b, hw, c = DEPTHWISE[layer]

    def fwd(x, w, bias, norm):
        return conv2d_lb(x, w, bias, groups=c, padding=3, norm=norm,
                         target=COMPILED)

    assert _compile(fwd, one_chip, (b, hw, hw, c), (7, 7, 1, c), (c,),
                    (2, c)) == 1


@pytest.mark.parametrize("layer", ["pw1", "pw2", "stem"])
def test_convnext_epilogue_kernels_compile(one_chip, layer):
    """GELU (erf from multiply-adds and a divide), layer scale +
    residual + LayerNorm, and the Ci=3 stride-4 stem with its norm."""
    if layer == "pw1":
        def fwd(x, w, bias):
            return conv2d_lb(x, w, bias, gelu=True, target=COMPILED)
        shapes = [(8, 56, 56, 96), (1, 1, 96, 384), (384,)]
    elif layer == "pw2":
        def fwd(x, w, bias, scale, res, norm):
            return conv2d_lb(x, w, bias, res, scale=scale, norm=norm,
                             target=COMPILED)
        shapes = [(8, 56, 56, 384), (1, 1, 384, 96), (96,), (96,),
                  (8, 56, 56, 96), (2, 96)]
    else:
        def fwd(x, w, bias, norm):
            return conv2d_lb(x, w, bias, stride=4, norm=norm,
                             target=COMPILED)
        shapes = [(8, 224, 224, 3), (4, 4, 3, 96), (96,), (2, 96)]
    assert _compile(fwd, one_chip, *shapes) == 1


def test_the_depthwise_kernel_carries_its_name(one_chip):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in ((8, 14, 14, 384), (7, 7, 1, 384))]
    hlo = jax.jit(lambda x, w: conv2d_lb(
        x, w, groups=384, padding=3, target=COMPILED)).lower(
            *args).compile().as_text()
    calls = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1
    assert calls[0].split(" = ")[0].split()[-1].startswith("%conv_dw")
