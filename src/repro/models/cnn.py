"""Conv-network model builders over the :mod:`repro.models.graph` IR.

VGG (the paper's own evaluation workload) is now just a
:class:`~repro.models.graph.ConvGraph` builder — the ``vgg_*``
functions are thin compat wrappers over the generic graph walk — and
ResNet BasicBlock stacks (:func:`resnet_graph`) and ConvNeXt
(:func:`convnext_graph`: depthwise 7x7 convs, LayerNorm, GELU, layer
scale, a 4x4 patch stem) ride the same IR: stride-2 downsampling
convs, 1x1 projection shortcuts and residual joins all flow through
the one planner/forward/accounting surface.

The conv layers run through :mod:`repro.kernels.conv_lb.ops` (the
spatially-tiled Pallas kernel realizing the paper's dataflow) when
requested, or ``jax.lax.conv_general_dilated`` otherwise; both are
numerically checked against each other in tests.

Init is He (Kaiming): each ReLU halves activation variance, so without
the sqrt(2) gain a 13-layer stack attenuates the signal
~sqrt(2)^13 ~= 90x and training plateaus at the entropy floor (the
exact failure tests used to show: loss stuck at ~ln(n_classes)).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from repro.core.vgg import _CFG
from repro.models.graph import (ConvGraph, ConvNode, dense_init,
                                graph_forward, graph_logits,
                                graph_plan_handles, graph_stages,
                                graph_training_step_report, init_graph,
                                split_keys)


def vgg_layer_dims(width_mult: float = 1.0):
    dims = []
    for name, ci, co, h, w in _CFG:
        dims.append((name, max(1, int(ci * width_mult)) if ci != 3 else 3,
                     max(1, int(co * width_mult)), h, w))
    return dims


def init_vgg(key, n_classes: int = 10, width_mult: float = 1.0,
             dtype=jnp.float32):
    dims = vgg_layer_dims(width_mult)
    keys = split_keys(key, len(dims) + 1)
    convs = []
    for k, (name, ci, co, _, _) in zip(keys, dims):
        convs.append({
            # He gain: preserves activation variance through ReLU depth
            "w": dense_init(k, (3, 3, ci, co), dtype,
                            fan_in=9 * ci) * math.sqrt(2.0),
            "b": jnp.zeros((co,), dtype),
        })
    last_co = dims[-1][2]
    return {"convs": convs,
            "head": dense_init(keys[-1], (last_co, n_classes), dtype,
                               fan_in=last_co)}


_POOL_AFTER = {"conv1_2", "conv2_2", "conv3_3", "conv4_3", "conv5_3"}


def vgg_graph(params, name: str = "vgg") -> ConvGraph:
    """The VGG stack the params realize, as a :class:`ConvGraph`.

    Channel counts come from the param shapes (params may be built
    with any ``width_mult``), the pool cadence from the VGG-16 config
    — the graph walk then resolves plane sizes and pool fusion exactly
    as the forward will execute them."""
    nodes = []
    for p, (cfg_name, *_rest) in zip(params["convs"], _CFG):
        ci, co = int(p["w"].shape[2]), int(p["w"].shape[3])
        nodes.append(ConvNode(name=cfg_name, ci=ci, co=co,
                              pool=2 if cfg_name in _POOL_AFTER else 1))
    return ConvGraph(name=name, nodes=tuple(nodes))


@dataclasses.dataclass(frozen=True)
class ConvStage:
    """One conv layer of the stack as the forward pass will execute it
    for a given input-plane geometry (legacy VGG view of the generic
    :class:`~repro.models.graph.GraphStage`)."""

    name: str
    ci: int
    co: int
    h: int             # input plane entering this layer
    w: int
    pool: bool         # a 2x2 maxpool follows this layer
    fused_pool: bool   # ... and the kernel path fuses it in-epilogue


def vgg_conv_geometry(params, h: int, w: int, in_ch: int = 3, *,
                      strict: bool = False) -> list[ConvStage]:
    """Walk the conv stack for an (h, w, in_ch) image.

    Thin wrapper over :func:`repro.models.graph.graph_stages` — the
    one walk shared by forward, plan handles and bounds, so plans and
    traffic charged off it match the executed jaxpr.  ``strict=False``
    (the historical default here) truncates the stack at the first
    channel mismatch — the reduced-width smoke-path compat mode; the
    generic graph walk errors instead unless truncation is opted into.
    """
    return [ConvStage(name=st.node.name, ci=st.node.ci, co=st.node.co,
                      h=st.h, w=st.w, pool=st.pool > 1,
                      fused_pool=st.fused_pool)
            for st in graph_stages(vgg_graph(params), h, w, in_ch,
                                   strict=strict)]


def vgg_conv_layers_for(params, h: int, w: int, *, batch: int,
                        in_ch: int = 3):
    """The stack as :class:`repro.core.layer.ConvLayer` workloads at an
    arrival batch — the analytic side of the serve ledger."""
    from repro.core.layer import ConvLayer

    return [ConvLayer(name=g.name, batch=batch, ci=g.ci, co=g.co,
                      hi=g.h, wi=g.w, hk=3, wk=3, stride=1, pad=1)
            for g in vgg_conv_geometry(params, h, w, in_ch)]


def vgg_plan_handles(params, h: int, w: int, *, batch: int,
                     in_ch: int = 3, dtype_bytes: int = 4,
                     vmem_budget: int | None = None,
                     training: bool = False):
    """Exported plan handles: [(ConvLayer, ConvPlan)] per conv stage at
    this arrival batch — :func:`graph_plan_handles` over the VGG graph
    (see there for the ``vmem_budget``/``training`` semantics)."""
    return graph_plan_handles(vgg_graph(params), h, w, batch=batch,
                              in_ch=in_ch, dtype_bytes=dtype_bytes,
                              vmem_budget=vmem_budget, training=training,
                              strict=False)


def vgg_training_step_report(params, h: int, w: int, *, batch: int,
                             in_ch: int = 3, dtype_bytes: int = 4,
                             vmem_budget: int | None = None) -> dict:
    """Per-training-step traffic accounting for the VGG conv stack —
    :func:`graph_training_step_report` over the VGG graph."""
    return graph_training_step_report(
        vgg_graph(params), h, w, batch=batch, in_ch=in_ch,
        dtype_bytes=dtype_bytes, vmem_budget=vmem_budget, strict=False)


def vgg_forward(params, images, target=None):
    """images: (B, H, W, 3) -> logits (B, n_classes).

    Batch-polymorphic: the kernel path re-plans (memoized) per arrival
    batch, so a serving bucket of b images folds straight into the
    kernel's ``b_block`` tiling dimension.  ``target`` (an
    :class:`~repro.core.exec_target.ExecTarget` or name; default
    ``LAX``) picks the backend: under a kernel target the conv layers
    run the batch-folded Pallas kernel with the bias/relu/(2x2
    maxpool) epilogue *fused* — each layer issues a single HBM output
    write instead of the unfused
    ``conv-write -> read -> bias/relu/pool -> write`` round trip."""
    return graph_logits(vgg_graph(params), params, images,
                        target=target, strict=False)


def vgg_loss(params, batch, target=None):
    logits = vgg_forward(params, batch["images"], target)
    labels = batch["labels"]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=1)
    return nll.mean()


# --------------------------------------------------------------------------
# ResNet BasicBlock stacks — the first strided/1x1 layers through the
# model-level planner end to end
# --------------------------------------------------------------------------

def resnet_graph(blocks=(3, 3, 3), widths=(16, 32, 64), in_ch: int = 3,
                 width_mult: float = 1.0,
                 name: str | None = None) -> ConvGraph:
    """CIFAR-style ResNet of BasicBlocks as a :class:`ConvGraph`.

    One 3x3 stem, then ``blocks[i]`` BasicBlocks at ``widths[i]``
    channels per stage; every stage after the first opens with a
    stride-2 downsampling block whose shortcut is a 1x1 stride-2
    projection conv (the canonical option-B shortcut).  Each block is

        x -> conv3x3(stride s) + ReLU -> conv3x3 -> (+ shortcut) -> ReLU

    with the join expressed as the second conv's ``residual`` edge —
    the kernel path fuses the add into the psum-resident epilogue.
    Defaults build ResNet-20 (3 stages x 3 blocks x 2 convs + stem);
    ``width_mult`` scales channel widths for smoke-size stacks."""
    widths = tuple(max(1, int(round(w * width_mult))) for w in widths)
    if name is None:
        name = f"resnet{2 + 2 * sum(blocks)}"
    nodes = [ConvNode(name="stem", ci=in_ch, co=widths[0])]
    prev = "stem"
    ci = widths[0]
    for si, (n_blocks, co) in enumerate(zip(blocks, widths), start=1):
        for bi in range(n_blocks):
            stride = 2 if si > 1 and bi == 0 else 1
            base = f"s{si}b{bi}"
            block_in = prev
            if stride != 1 or ci != co:
                nodes.append(ConvNode(name=f"{base}_proj", ci=ci, co=co,
                                      hk=1, wk=1, stride=stride, pad=0,
                                      relu=False, src=block_in))
                shortcut = f"{base}_proj"
            else:
                shortcut = block_in
            nodes.append(ConvNode(name=f"{base}_a", ci=ci, co=co,
                                  stride=stride, src=block_in))
            nodes.append(ConvNode(name=f"{base}_b", ci=co, co=co,
                                  residual=shortcut))
            prev = f"{base}_b"
            ci = co
    return ConvGraph(name=name, nodes=tuple(nodes))


def init_resnet(key, graph: ConvGraph | None = None,
                n_classes: int = 10, dtype=jnp.float32):
    """He-init params for a ResNet graph (default: ResNet-20); the
    ``{"convs", "head"}`` pytree shape shared with the VGG stack."""
    return init_graph(key, graph or resnet_graph(), n_classes=n_classes,
                      dtype=dtype)


def resnet_forward(graph: ConvGraph, params, images, target=None):
    """images: (B, H, W, in_ch) -> logits — :func:`graph_logits` over a
    ResNet graph (residual joins fused on the kernel path); ``target``
    selects the execution backend."""
    return graph_logits(graph, params, images, target=target)


# --------------------------------------------------------------------------
# ConvNeXt — depthwise 7x7 convs, LayerNorm, GELU and layer scale
# --------------------------------------------------------------------------

def convnext_graph(depths=(3, 3, 9, 3), widths=(96, 192, 384, 768),
                   in_ch: int = 3, name: str = "convnext_t") -> ConvGraph:
    """ConvNeXt (Liu et al., arXiv:2201.03545, Sec. 2 and Table 9) as a
    :class:`ConvGraph`; the defaults build ConvNeXt-T, 58 conv nodes.

    Nodes, in order:

      stem          4x4 stride-4 conv in_ch -> C1, LayerNorm
      s{i}_down     (stages 2-4) 2x2 stride-2 conv C(i-1) -> Ci
      s{i}b{j}_dw   7x7 depthwise conv (pad 3), LayerNorm
      s{i}b{j}_pw1  1x1 conv C -> 4C, GELU
      s{i}b{j}_pw2  1x1 conv 4C -> C, layer scale, + the block's input

    then mean pool, LayerNorm and a linear head with bias.  The
    LayerNorm before each downsampling conv is the epilogue of the
    preceding ``pw2`` (``norm``): that block output has no other
    consumer, so normalizing it in place is exact.  Every LayerNorm is
    over the channels with eps 1e-6.  Drop path is the identity at
    inference and is not represented.

    Parameters (:func:`init_convnext`, or any pytree of this layout):
    ``{"convs": [...], "head": (C4, classes), "head_b": (classes,),
    "head_norm_w": (C4,), "head_norm_b": (C4,)}``, one ``convs`` entry
    per node in the order above, each ``{"w": (k, k, ci / groups, co),
    "b": (co,)}`` plus ``"scale": (co,)`` on ``pw2`` nodes and
    ``"norm_w"``, ``"norm_b"``: (co,) on nodes with ``norm``.  A
    depthwise kernel is (7, 7, 1, C)."""
    nodes = [ConvNode(name="stem", ci=in_ch, co=widths[0], hk=4, wk=4,
                      stride=4, pad=0, relu=False, norm=True)]
    prev = "stem"
    for si, (depth, c) in enumerate(zip(depths, widths), start=1):
        if si > 1:
            nodes.append(ConvNode(name=f"s{si}_down", ci=widths[si - 2],
                                  co=c, hk=2, wk=2, stride=2, pad=0,
                                  relu=False))
            prev = f"s{si}_down"
        for bi in range(depth):
            base = f"s{si}b{bi}"
            last = bi == depth - 1 and si < len(depths)
            nodes += [
                ConvNode(name=f"{base}_dw", ci=c, co=c, hk=7, wk=7,
                         pad=3, groups=c, relu=False, norm=True),
                ConvNode(name=f"{base}_pw1", ci=c, co=4 * c, hk=1, wk=1,
                         pad=0, relu=False, gelu=True),
                ConvNode(name=f"{base}_pw2", ci=4 * c, co=c, hk=1, wk=1,
                         pad=0, relu=False, scale=True, residual=prev,
                         norm=last)]
            prev = f"{base}_pw2"
    return ConvGraph(name=name, nodes=tuple(nodes), head_norm=True)


def init_convnext(key, graph: ConvGraph | None = None,
                  n_classes: int = 1000, dtype=jnp.float32):
    """Params of a ConvNeXt graph (default ConvNeXt-T) in
    :func:`convnext_graph`'s layout: He-init convs, layer scales at the
    published 1e-6, identity norms, zero biases."""
    return init_graph(key, graph or convnext_graph(), n_classes=n_classes,
                      dtype=dtype)


__all__ = [
    "ConvStage", "init_vgg", "vgg_layer_dims", "vgg_graph",
    "vgg_conv_geometry", "vgg_conv_layers_for", "vgg_plan_handles",
    "vgg_training_step_report", "vgg_forward", "vgg_loss",
    "resnet_graph", "init_resnet", "resnet_forward",
    "convnext_graph", "init_convnext",
    # re-exported graph surface (the model-agnostic consumers)
    "ConvGraph", "ConvNode", "graph_forward", "graph_logits",
    "graph_plan_handles", "graph_stages", "graph_training_step_report",
    "init_graph",
]
