"""Model-agnostic conv-graph IR: one graph, three consumers.

The paper's Eq. (15) bound and the whole `plan_conv` machinery are
per-conv-layer, so any conv network's step bound is a *sum over its
layers* — but only if something model-agnostic can walk the network.
This module is that walk: a :class:`ConvGraph` of :class:`ConvNode`\\ s,
each carrying its full conv geometry (kernel extent, stride, padding,
groups), an epilogue spec (bias, GELU, layer scale, relu, a LayerNorm
over the channels, pool), and an optional residual input edge, plus one generic geometry resolution
(:func:`graph_stages`) that every consumer shares:

  * :func:`graph_forward` — the executable forward (Pallas kernel or
    lax path; residual adds applied at the join, fused into the
    kernel's psum-resident epilogue where shapes allow);
  * :func:`graph_plan_handles` — the ``(ConvLayer, ConvPlan)`` (or
    training-triple) accounting handles the serve ledger and the
    training-step report charge traffic off;
  * :func:`graph_training_step_report` — per-step fwd+dgrad+wgrad
    bytes vs the per-graph ``q_dram_training`` sum, strided and
    grouped layers included (``plan_conv_training`` plans their
    dgrad/wgrad even where execution falls back to lax).

Because plans, forward and bounds all derive from the *same* stage
walk, the bytes the ledger charges are the bytes the executed jaxpr
moves — the same single-source-of-truth contract ``vgg_conv_geometry``
gave the VGG stack, now for any conv network (ResNet BasicBlocks with
stride-2 downsampling and 1x1 projection shortcuts are the proving
workload; see :func:`repro.models.cnn.resnet_graph`).

Topology: nodes are listed in topological order; each node consumes
``src`` (a prior node's name, or :data:`GRAPH_INPUT`; ``None`` chains
to the immediately preceding node) and may name a ``residual`` tensor
added to its conv output *before* the ReLU/pool epilogue — exactly
the BasicBlock join, and (after GELU and the layer scale) the ConvNeXt
block's.  The walk validates every edge's plane/channel
shapes; a channel mismatch is an error unless ``strict=False``
(opt-in truncation, the reduced-width smoke-stack compat mode).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

GRAPH_INPUT = "input"


@dataclasses.dataclass(frozen=True)
class ConvNode:
    """One conv layer of a :class:`ConvGraph`.

    ``src`` names the producing tensor (``None`` = previous node,
    :data:`GRAPH_INPUT` = the graph input); ``residual`` optionally
    names a tensor added to the conv output before the ReLU — the
    residual join.  ``pool`` is an aligned ``pool x pool`` max-pool
    after the epilogue (fused into the kernel when the output plane
    divides it; skipped entirely when the plane is smaller than the
    window, matching the VGG walk's small-plane behavior).

    The epilogue runs, in order: bias, exact (erf) ``gelu``, a
    per-channel layer ``scale`` (weights ``"scale"``), the residual
    join, ``relu``, a LayerNorm over the output channels (``norm``;
    weights ``"norm_w"``, ``"norm_b"``, eps 1e-6), the pool.  A node
    with ``gelu``, ``scale`` and ``norm`` unset traces what it traced
    before they existed.  ``norm`` on a grouped node needs the
    depthwise kernel's geometry (``groups == ci == co``, stride 1, no
    pool or residual), whose channel block sees every channel."""

    name: str
    ci: int
    co: int
    hk: int = 3
    wk: int = 3
    stride: int = 1
    pad: int = 1
    groups: int = 1
    bias: bool = True
    relu: bool = True
    pool: int = 1
    src: str | None = None
    residual: str | None = None
    gelu: bool = False
    scale: bool = False
    norm: bool = False

    @property
    def depthwise(self) -> bool:
        """One input channel per output channel: the ``conv_dw``
        kernel's layer (``groups == ci == co``)."""
        return self.groups > 1 and self.groups == self.ci == self.co


@dataclasses.dataclass(frozen=True)
class ConvGraph:
    """A conv network as a topologically-ordered tuple of nodes.

    Hashable (frozen, tuple-of-frozen), so a graph can key plan-handle
    caches directly.  The graph output is the last node's tensor; the
    classifier mean-pools it and applies the linear ``"head"``.  With
    ``head_norm`` it is ConvNeXt's classifier: a LayerNorm first
    (weights ``"head_norm_w"``, ``"head_norm_b"``) and a bias
    ``"head_b"`` after."""

    name: str
    nodes: tuple[ConvNode, ...]
    head_norm: bool = False

    def __post_init__(self):
        seen = {GRAPH_INPUT}
        for node in self.nodes:
            if node.name in seen:
                raise ValueError(f"duplicate node name {node.name!r}")
            for ref in (node.src, node.residual):
                if ref is not None and ref not in seen:
                    raise ValueError(
                        f"node {node.name!r} references {ref!r} before "
                        f"it is produced (nodes must be topological)")
            if node.ci % node.groups or node.co % node.groups:
                raise ValueError(f"node {node.name!r}: groups="
                                 f"{node.groups} must divide ci={node.ci}"
                                 f" and co={node.co}")
            if node.norm and node.groups > 1 and not (
                    node.depthwise and node.stride == 1 and node.pool == 1
                    and node.residual is None):
                raise ValueError(f"node {node.name!r}: a LayerNorm over "
                                 f"a grouped conv needs the depthwise "
                                 f"kernel's geometry")
            seen.add(node.name)

    @property
    def out_channels(self) -> int:
        return self.nodes[-1].co


@dataclasses.dataclass(frozen=True)
class GraphStage:
    """One node resolved against a concrete input-plane geometry: the
    layer exactly as :func:`graph_forward` will execute it (and hence
    exactly what the plan handles account)."""

    node: ConvNode
    h: int              # input plane entering the conv
    w: int
    ho: int             # conv output plane (pre-pool)
    wo: int
    pool: int           # effective pool (1 = none; plane too small)
    fused_pool: bool    # kernel path fuses the pool in-epilogue
    residual: bool      # a residual join lands on this node's output


def graph_stages(graph: ConvGraph, h: int, w: int, in_ch: int = 3, *,
                 strict: bool = True) -> list[GraphStage]:
    """Resolve the graph against an ``(h, w, in_ch)`` input image.

    The single source of truth shared by :func:`graph_forward`, the
    plan-handle export and the bound sums.  ``strict=True`` (default)
    raises on any channel mismatch along the walk; ``strict=False``
    truncates the stack at the first mismatch instead — the explicit
    opt-in that replaces ``vgg_conv_geometry``'s silent truncation
    (reduced-width smoke stacks ride it via the ``vgg_*`` wrappers).
    """
    shapes: dict[str, tuple[int, int, int]] = {GRAPH_INPUT: (h, w, in_ch)}
    prev = GRAPH_INPUT
    stages: list[GraphStage] = []
    for node in graph.nodes:
        h0, w0, c0 = shapes[node.src or prev]
        if c0 != node.ci:
            if strict:
                raise ValueError(
                    f"node {node.name!r} expects ci={node.ci} but its "
                    f"input {node.src or prev!r} carries {c0} channels "
                    f"(pass strict=False to truncate the walk here)")
            break
        ho = (h0 + 2 * node.pad - node.hk) // node.stride + 1
        wo = (w0 + 2 * node.pad - node.wk) // node.stride + 1
        if ho < 1 or wo < 1:
            raise ValueError(f"node {node.name!r}: {node.hk}x{node.wk} "
                             f"s{node.stride} conv has no output on a "
                             f"{h0}x{w0} plane")
        if node.residual is not None:
            rshape = shapes[node.residual]
            if rshape != (ho, wo, node.co):
                raise ValueError(
                    f"node {node.name!r}: residual {node.residual!r} is "
                    f"{rshape}, join needs {(ho, wo, node.co)}")
        pool = node.pool if node.pool > 1 and min(ho, wo) >= node.pool else 1
        fused = pool > 1 and ho % pool == 0 and wo % pool == 0
        stages.append(GraphStage(node=node, h=h0, w=w0, ho=ho, wo=wo,
                                 pool=pool, fused_pool=fused,
                                 residual=node.residual is not None))
        shapes[node.name] = (ho // pool, wo // pool, node.co)
        prev = node.name
    return stages


def dense_init(key: jax.Array, shape: tuple[int, ...],
               dtype, fan_in: int | None = None) -> jax.Array:
    fan_in = fan_in if fan_in is not None else shape[0]
    std = 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def split_keys(key: jax.Array, n: int) -> list[jax.Array]:
    return list(jax.random.split(key, n))


def init_graph(key, graph: ConvGraph, n_classes: int = 10,
               dtype=jnp.float32) -> dict:
    """He-init conv params for every node + a linear head off the graph
    output channels.  Returns the same ``{"convs": [...], "head": ...}``
    pytree shape the VGG stack uses, so one training/serving surface
    covers every graph.  ReLU nodes get the sqrt(2) gain (each ReLU
    halves activation variance); linear nodes (e.g. 1x1 projection
    shortcuts) stay at plain He.  Layer scales start at ConvNeXt's
    1e-6, norms at the identity (weight 1, bias 0), a head bias at 0."""
    keys = split_keys(key, len(graph.nodes) + 1)
    convs = []
    for k, node in zip(keys, graph.nodes):
        fan_in = node.hk * node.wk * (node.ci // node.groups)
        gain = math.sqrt(2.0) if node.relu else 1.0
        p = {"w": dense_init(k, (node.hk, node.wk,
                                 node.ci // node.groups, node.co),
                             dtype, fan_in=fan_in) * gain}
        if node.bias:
            p["b"] = jnp.zeros((node.co,), dtype)
        if node.scale:
            p["scale"] = jnp.full((node.co,), 1e-6, dtype)
        if node.norm:
            p["norm_w"] = jnp.ones((node.co,), dtype)
            p["norm_b"] = jnp.zeros((node.co,), dtype)
        convs.append(p)
    co = graph.out_channels
    params = {"convs": convs,
              "head": dense_init(keys[-1], (co, n_classes), dtype,
                                 fan_in=co)}
    if graph.head_norm:
        params["head_norm_w"] = jnp.ones((co,), dtype)
        params["head_norm_b"] = jnp.zeros((co,), dtype)
        params["head_b"] = jnp.zeros((n_classes,), dtype)
    return params


def graph_forward(graph: ConvGraph, conv_params, x, *,
                  target=None, strict: bool = True,
                  tracer=None):
    """Execute the graph on ``x`` (B, H, W, Ci) -> (B, H', W', Co).

    ``conv_params`` aligns with ``graph.nodes`` (``{"w": ..., "b":}``
    per node).  ``target`` (an
    :class:`~repro.core.exec_target.ExecTarget` or name; default
    ``LAX``) picks the backend for every conv: under a kernel target
    (``interpret``/``compiled``) each conv runs the batch-folded
    Pallas kernel with its epilogue *fused* — bias, the residual join
    (added on the VMEM-resident psum tile, so the shortcut costs one
    streamed read instead of an extra HBM round trip), ReLU and an
    aligned pool; non-pool-aligned planes take the rare unfused pool.
    ``LAX`` rides ``conv2d_lb``'s reference path (f32-accumulating
    conv + unfused epilogue), so the two paths can never drift apart;
    a ``compiled`` layer with no mosaic-legal plan degrades to it
    per-layer with a traced event.

    ``tracer`` (default: the ambient tracer) records one synced
    per-layer span — seconds *and* the plan's accounted bytes — but
    only when executing eagerly: inside a jit trace spans would time
    tracing, not running, so instrumentation turns itself off."""
    from repro.core.exec_target import LAX, resolve_target
    from repro.kernels.conv_lb.ops import conv2d_lb, conv2d_lb_timed
    from repro.obs.tracer import NULL_SPAN as _NULL_CTX
    from repro.obs.tracer import active_tracer

    tgt = resolve_target(target, default=LAX)
    if not tgt.compute:
        raise ValueError("graph_forward executes the graph; an "
                         "account-only target belongs to the serve "
                         "ledger, not here")
    tr = active_tracer() if tracer is None else tracer
    # per-layer timing is only honest outside a jit trace
    timing = tr.active and not isinstance(x, jax.core.Tracer)
    stages = graph_stages(graph, x.shape[1], x.shape[2], x.shape[3],
                          strict=strict)
    tensors = {GRAPH_INPUT: x}
    prev = GRAPH_INPUT
    out = x
    fwd_span = (tr.span("graph.forward", model=graph.name,
                        batch=x.shape[0], mode=tgt.name)
                if timing else _NULL_CTX)
    with fwd_span:
        for p, st in zip(conv_params, stages):
            node = st.node
            src = tensors[node.src or prev]
            res = (None if node.residual is None
                   else tensors[node.residual])
            bias = p.get("b") if node.bias else None
            kw = dict(stride=node.stride, padding=node.pad,
                      groups=node.groups, relu=node.relu,
                      pool=st.pool if st.fused_pool else 1,
                      target=tgt)
            if node.gelu:
                kw["gelu"] = True
            if node.scale:
                kw["scale"] = p["scale"]
            if node.norm:
                kw["norm"] = jnp.stack([p["norm_w"], p["norm_b"]])
            # the layer's name on its device ops (HLO op_name), in
            # the forward and in the backward that differentiates it
            with jax.named_scope(node.name):
                if timing:
                    with tr.span("graph.layer", layer=node.name,
                                 model=graph.name):
                        y = conv2d_lb_timed(src, p["w"], bias, res,
                                            tracer=tr, **kw)
                else:
                    y = conv2d_lb(src, p["w"], bias, res, **kw)
            if st.pool > 1 and not st.fused_pool:
                y = jax.lax.reduce_window(
                    y, -jnp.inf, jax.lax.max, (1, st.pool, st.pool, 1),
                    (1, st.pool, st.pool, 1), "VALID")
            tensors[node.name] = y
            prev = node.name
            out = y
    return out


def graph_logits(graph: ConvGraph, params, images, *,
                 target=None, strict: bool = True):
    """Full classification forward: graph features, global mean pool,
    linear head (ConvNeXt's LayerNorm before it and bias after it with
    ``graph.head_norm``) — ``params`` from :func:`init_graph` (or any
    pytree of the same shape).  ``target`` selects the
    execution backend exactly as in :func:`graph_forward`."""
    h = graph_forward(graph, params["convs"], images,
                      target=target, strict=strict)
    feats = h.mean(axis=(1, 2))
    if not graph.head_norm:
        return feats @ params["head"]
    from repro.kernels.conv_lb.kernel import layer_norm
    feats = layer_norm(feats, params["head_norm_w"], params["head_norm_b"])
    return feats @ params["head"] + params["head_b"]


def graph_plan_handles(graph: ConvGraph, h: int, w: int, *, batch: int,
                       in_ch: int = 3, dtype_bytes: int = 4,
                       vmem_budget: int | None = None,
                       training: bool = False, strict: bool = True,
                       verify: bool = False, target: str = "interpret"):
    """Exported accounting handles for the whole graph at an arrival
    batch: ``[(ConvLayer, ConvPlan)]`` per conv stage, from the same
    memoized ``plan_conv`` cache the kernel path's jit trace resolves
    against.  A depthwise node exports one
    :class:`~repro.kernels.conv_lb.depthwise.DepthwisePlan` handle (its
    layer carries ``groups``; the same handle in a training export,
    whose grouped backward runs the lax VJP).  Other grouped nodes
    export one per-*group* handle per group (traffic and bound both
    scale by the group count, exactly as the kernel executes them).  Strided and 1x1 layers flow through the
    same planner — nothing above this walk is VGG-shaped.

    ``training=True`` exports ``(ConvLayer, ConvTrainingPlan)``
    instead: the forward handle plus the planned dgrad/wgrad convs of
    each layer's backward (``plan_conv_training``), so strided
    downsample convs get accounted dgrad/wgrad even though their
    execution rides the lax fallback.

    ``vmem_budget=None`` yields the kernel's own execution plans; an
    explicit budget (e.g. the paper's 1 MiB GBuf) yields the
    accounting plans the ledger scores distance-to-bound with.

    ``target`` is the legality profile every plan is made for
    (``"mosaic"`` yields the compiled kernels' own plans).

    ``verify=True`` runs the exported handles through the static
    verifier (:func:`repro.analysis.plan_check.audit_handles`) and
    raises :class:`~repro.analysis.plan_check.PlanLegalityError` on
    any structural finding or accountant drift — the gate
    :class:`~repro.serve.server.ImageServer` applies before a plan
    set enters its cache.
    """
    from repro.core.layer import ConvLayer
    from repro.kernels.conv_lb.depthwise import plan_conv_dw
    from repro.kernels.conv_lb.ops import plan_conv, plan_conv_training

    handles = []
    for st in graph_stages(graph, h, w, in_ch, strict=strict):
        node = st.node
        if node.depthwise and node.stride == 1 and st.pool == 1 \
                and not st.residual:
            layer = ConvLayer(name=node.name, batch=batch, ci=node.ci,
                              co=node.co, hi=st.h, wi=st.w, hk=node.hk,
                              wk=node.wk, pad=node.pad,
                              groups=node.groups)
            handles.append((layer, plan_conv_dw(
                st.h, st.w, node.ci, node.hk, node.wk,
                padding=(node.pad,) * 2, norm=node.norm,
                dtype_bytes=dtype_bytes, vmem_budget=vmem_budget,
                target=target)))
            continue
        ci_g, co_g = node.ci // node.groups, node.co // node.groups
        layer = ConvLayer(name=node.name, batch=batch, ci=ci_g, co=co_g,
                          hi=st.h, wi=st.w, hk=node.hk, wk=node.wk,
                          stride=node.stride, pad=node.pad)
        plan = plan_conv(st.h, st.w, ci_g, co_g, node.hk, node.wk,
                         batch=batch, stride=(node.stride,) * 2,
                         padding=(node.pad,) * 2,
                         pool=st.pool if st.fused_pool else 1,
                         residual=st.residual,
                         norm=node.norm and node.groups == 1,
                         dtype_bytes=dtype_bytes,
                         vmem_budget=vmem_budget, target=target)
        if training:
            entry = (layer, plan_conv_training(
                plan, batch=batch, groups=node.groups,
                dtype_bytes=dtype_bytes, vmem_budget=vmem_budget))
        else:
            entry = (layer, plan)
        handles.extend([entry] * node.groups)
    if verify:
        from repro.analysis.plan_check import (Diagnostic,
                                               PlanLegalityError,
                                               audit_handles)
        audit = audit_handles(handles, batch=batch,
                              dtype_bytes=dtype_bytes,
                              vmem_budget=vmem_budget, target=target)
        if not audit.ok:
            diags = audit.errors() or [Diagnostic(
                rule="audit.traffic", severity="error",
                message=audit.report())]
            raise PlanLegalityError(diags)
    return handles


def graph_training_step_report(graph: ConvGraph, h: int, w: int, *,
                               batch: int, in_ch: int = 3,
                               dtype_bytes: int = 4,
                               vmem_budget: int | None = None,
                               strict: bool = True,
                               tracer=None) -> dict:
    """Per-training-step traffic accounting for any conv graph.

    Sums every layer's planned fwd+dgrad+wgrad words
    (:meth:`ConvTrainingPlan.traffic`) and scores them against the
    per-graph ``q_dram_training`` sum, each pass's Eq. (15) term at
    its realized plan footprint (residual joins add their mandatory
    read to both sides) — the training counterpart of the serve
    ledger's ``vs_bound_x``, for heterogeneous stacks."""
    from repro.obs.tracer import active_tracer

    tr = active_tracer() if tracer is None else tracer
    with tr.span("graph.training_report", model=graph.name,
                 batch=batch) as _sp:
        handles = graph_plan_handles(graph, h, w, batch=batch,
                                     in_ch=in_ch,
                                     dtype_bytes=dtype_bytes,
                                     vmem_budget=vmem_budget,
                                     training=True, strict=strict)
        words = fwd_words = bound = 0.0
        kernel_layers = 0
        for layer, tp in handles:
            t = tp.traffic(batch)
            words += t.total
            # a depthwise handle is its forward alone
            fwd_words += t.fwd.total if hasattr(t, "fwd") else t.total
            bound += tp.bound_words(layer)
            # grouped layers repeat per group but never ride the kernel
            # dgrad (dgrad_kernel is gated on groups == 1), so the sum
            # counts each kernel-dgrad layer exactly once
            kernel_layers += int(getattr(tp, "dgrad_kernel", False))
        n_stages = len(graph_stages(graph, h, w, in_ch, strict=strict))
        _sp.set(traffic_bytes=words * dtype_bytes,
                train_vs_bound_x=words / max(bound, 1e-30))
        return {
            "model": graph.name,
            "layers": n_stages,
            "dgrad_kernel_layers": kernel_layers,
            "dgrad_kernel_frac": kernel_layers / max(1, len(handles)),
            "bytes_per_step": words * dtype_bytes,
            "bound_bytes_per_step": bound * dtype_bytes,
            "train_vs_bound_x": words / max(bound, 1e-30),
            "bwd_share": (words - fwd_words) / max(words, 1e-30),
        }
