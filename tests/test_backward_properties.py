"""Property sweep: the *executing* backward pass vs the lax VJP.

The tentpole claim is that gradients no longer merely *plan* through
the paper dataflow but execute through it: dgrad as the lhs-dilated
compact-plane walk of the forward kernel (any stride), wgrad through
the dW-stationary kernel — under the Pallas interpreter, on both the
interpret-profile plans and the mosaic plans a TPU run executes.
These properties sweep random geometries
(stride, kernel size, padding) and require (a) grads match the lax
VJP to 1e-4 and (b) zero ``exec.fallback`` tallies, so the match is
evidence about the kernels, not about a quiet lax escape.  A final
fetch-count check pins the executing wgrad's ``kernel.wgrad`` traffic
event to ``WgradPlan.traffic`` word for word.
"""

import dataclasses

import jax
import jax.numpy as jnp

from _hypothesis_compat import given, settings, st

from repro.core.exec_target import INTERPRET, LAX
from repro.kernels.conv_lb.ops import (conv2d_lb, exec_fallback_counts,
                                       plan_conv, plan_conv_wgrad,
                                       reset_fallback_counts)
from repro.kernels.conv_lb.wgrad import wgrad_lb_call
from repro.obs import Tracer

MB = 1 << 20
TOL = 1e-4
# the interpreter running the plans a compiled (Mosaic) run would run
MOSAIC_INTERPRET = dataclasses.replace(INTERPRET, plan_target="mosaic")


def _grads(x, w, stride, pad, tgt):
    def loss(x_, w_):
        y = conv2d_lb(x_, w_, stride=stride, padding=pad, target=tgt)
        return (y ** 2).sum()

    return jax.grad(loss, argnums=(0, 1))(x, w)


@settings(max_examples=12, deadline=None)
@given(st.integers(6, 13), st.integers(6, 13),
       st.sampled_from([1, 3, 5]), st.sampled_from([1, 3]),
       st.sampled_from([1, 2, 3]), st.integers(0, 2))
def test_interpret_backward_matches_lax_vjp(h, w, hk, wk, stride,
                                            pad_idx):
    """Random (stride, hk, wk, padding): both grads through the
    interpreter's dgrad + wgrad kernels track the lax VJP, with no
    fallback recorded — the strided cases run the lhs-dilated plane."""
    if h < hk or w < wk:
        return
    py, px = min(pad_idx, hk - 1), min(pad_idx, wk - 1)
    key = jax.random.PRNGKey(h * 131 + w * 17 + hk * 7 + wk * 5
                             + stride * 3 + pad_idx)
    x = jax.random.normal(key, (2, h, w, 4))
    wgt = jax.random.normal(jax.random.fold_in(key, 1),
                            (hk, wk, 4, 6)) * 0.2
    reset_fallback_counts()
    gx, gw = _grads(x, wgt, stride, (py, px), INTERPRET)
    assert not exec_fallback_counts(), exec_fallback_counts()
    gx_l, gw_l = _grads(x, wgt, stride, (py, px), LAX)
    assert float(jnp.max(jnp.abs(gx - gx_l))) < TOL
    assert float(jnp.max(jnp.abs(gw - gw_l))) < TOL


@settings(max_examples=4, deadline=None)
@given(st.sampled_from([8, 12]), st.sampled_from([1, 3]),
       st.sampled_from([1, 2]), st.integers(0, 1))
def test_mosaic_plan_backward_matches_lax_vjp(h, hk, stride, pad_idx):
    """The same property on a lane-aligned geometry under the mosaic
    plans a compiled run executes (full-row sublane-padded x tiles,
    LANE channel blocks, sublane-padded wgrad strips), run by the
    interpreter: dgrad + wgrad match lax and nothing degrades to the
    lax VJP.  Mosaic compiles these kernels in test_mosaic_compile."""
    py = min(pad_idx, hk - 1)
    key = jax.random.PRNGKey(h * 29 + hk * 11 + stride * 5 + pad_idx)
    x = jax.random.normal(key, (1, h, h, 128))
    wgt = jax.random.normal(jax.random.fold_in(key, 1),
                            (hk, hk, 128, 128)) * 0.05
    reset_fallback_counts()
    gx, gw = _grads(x, wgt, stride, (py, py), MOSAIC_INTERPRET)
    assert not exec_fallback_counts(), exec_fallback_counts()
    gx_l, gw_l = _grads(x, wgt, stride, (py, py), LAX)
    assert float(jnp.max(jnp.abs(gx - gx_l))) < TOL
    assert float(jnp.max(jnp.abs(gw - gw_l))) < TOL


def test_wgrad_event_words_match_plan_traffic():
    """The ``kernel.wgrad`` event the executing call emits (realized
    grid x operand block volumes) equals ``WgradPlan.traffic`` exactly
    — the measured and the charged volume are the same integer."""
    plan = plan_conv(12, 12, 8, 6, 3, 3, batch=2, stride=(2, 2),
                     padding=(1, 1), vmem_budget=MB)
    wplan = plan_conv_wgrad(plan, vmem_budget=MB)
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(key, (2, 12, 12, 8))
    dy = jax.random.normal(jax.random.fold_in(key, 1),
                           (2, plan.ho, plan.wo, 6))
    tracer = Tracer()
    with tracer.activate():
        gw = wgrad_lb_call(x, dy, wplan)
        gw.block_until_ready()
    ev = [r for r in tracer.records if r.name == "kernel.wgrad"]
    assert len(ev) == 1
    assert ev[0].attrs["words_moved"] == int(wplan.traffic(2).total)
    assert ev[0].attrs["bytes_moved"] == 4 * int(wplan.traffic(2).total)
