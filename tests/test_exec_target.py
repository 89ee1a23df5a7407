"""ExecTarget: the one execution-backend switch (resolve / clamp /
ladder / legacy-flag adapter)."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.core.exec_target import (ACCOUNT_ONLY, COMPILED, INTERPRET,
                                    LAX, TARGETS, ExecTarget,
                                    from_flags, resolve_target)
from repro.kernels.conv_lb.ops import conv2d_lb, plan_conv
from repro.obs import Tracer


def test_canonical_targets_and_ranks():
    assert set(TARGETS) == {"interpret", "compiled", "lax",
                            "account-only"}
    assert (ACCOUNT_ONLY.rank < LAX.rank < INTERPRET.rank
            < COMPILED.rank)
    assert COMPILED.plan_target == "mosaic" and not COMPILED.interpret
    assert INTERPRET.interpret and INTERPRET.kernel
    assert not LAX.kernel and LAX.compute
    assert not ACCOUNT_ONLY.compute


def test_resolve_accepts_names_aliases_and_instances():
    assert resolve_target("compiled") is COMPILED
    assert resolve_target("mosaic") is COMPILED        # alias
    assert resolve_target("Account_Only") is ACCOUNT_ONLY
    assert resolve_target("account") is ACCOUNT_ONLY
    assert resolve_target(LAX) is LAX
    assert resolve_target(None, default=INTERPRET) is INTERPRET
    with pytest.raises(ValueError, match="unknown execution target"):
        resolve_target("gpu")
    with pytest.raises(ValueError, match="no execution target"):
        resolve_target(None)


def test_clamp_is_downward_only():
    """The one negotiation every boundary uses: a request can degrade
    a server's target but never upgrade it (the old
    ``self.use_kernel and bool(use_kernel)`` double-negotiation)."""
    assert INTERPRET.clamp(None) is INTERPRET
    assert INTERPRET.clamp("lax") is LAX                 # downgrade
    assert LAX.clamp("compiled") is LAX                  # no upgrade
    assert ACCOUNT_ONLY.clamp(COMPILED) is ACCOUNT_ONLY
    assert COMPILED.clamp(INTERPRET) is INTERPRET
    assert COMPILED.clamp(COMPILED) is COMPILED


def test_ladder_walks_down_to_account_only():
    assert COMPILED.ladder() == (COMPILED, LAX, ACCOUNT_ONLY)
    assert INTERPRET.ladder() == (INTERPRET, LAX, ACCOUNT_ONLY)
    assert LAX.ladder() == (LAX, ACCOUNT_ONLY)
    assert ACCOUNT_ONLY.ladder() == (ACCOUNT_ONLY,)


def test_from_flags_maps_the_legacy_boolean_triple():
    assert from_flags() is INTERPRET
    assert from_flags(use_kernel=False) is LAX
    assert from_flags(compute=False) is ACCOUNT_ONLY
    assert from_flags(compute=False, use_kernel=False) is ACCOUNT_ONLY
    assert from_flags(interpret=False) is COMPILED


def test_targets_are_frozen_hashable_and_jit_static_safe():
    assert {COMPILED: 1}[COMPILED] == 1                 # dict key
    assert str(LAX) == "lax"
    with pytest.raises(dataclasses.FrozenInstanceError):
        COMPILED.rank = 0


# --------------------------------------------------------------------------
# COMPILED means Mosaic: legality, loud fallback, and no silent CPU run
# --------------------------------------------------------------------------

def _xw(b=2, h=8, c=128, seed=0):
    k = jax.random.PRNGKey(seed)
    x = jax.random.normal(k, (b, h, h, c), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(k, 1), (3, 3, c, c),
                          jnp.float32) * 0.05
    return x, w


def test_compiled_target_without_a_tpu_raises():
    """No TPU attached: a COMPILED conv is an error, never a quiet run
    of the interpreter or of some other lowering."""
    x, w = _xw()
    with pytest.raises(ValueError, match="interpret mode"):
        conv2d_lb(x, w, padding=1, target=COMPILED)


def test_plans_remember_their_legality_target():
    p_i = plan_conv(10, 10, 24, 24, 3, 3, batch=1, padding=(1, 1))
    assert p_i.target == "interpret"
    p_m = plan_conv(8, 8, 128, 128, 3, 3, batch=2, padding=(1, 1),
                    target="mosaic")
    assert p_m.target == "mosaic"
    # explain() defaults to the plan's own stored profile
    assert "verifier [mosaic]" in p_m.explain()


def test_illegal_explicit_blocks_under_compiled_fall_back_loudly():
    """Fresh geometry (events fire at trace time): mosaic-illegal
    explicit blocks under COMPILED emit one ``exec.fallback`` and
    return the lax result — never a silent interpreter run."""
    k = jax.random.PRNGKey(7)
    x = jax.random.normal(k, (1, 12, 12, 24), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(k, 1),
                          (3, 3, 24, 24), jnp.float32) * 0.1
    tr = Tracer()
    with tr.activate():
        # x_block=6: not a sublane-aligned full row — mosaic-illegal,
        # interpret-legal
        y = conv2d_lb(x, w, padding=1, x_block=6, target="compiled")
    falls = [r for r in tr.records if r.name == "exec.fallback"]
    assert falls, "expected a traced exec.fallback"
    assert falls[0].attrs["target"] == "compiled"
    assert falls[0].attrs["to"] == "lax"
    yl = conv2d_lb(x, w, padding=1, target="lax")
    assert float(jnp.max(jnp.abs(y - yl))) < 1e-5


def test_interpret_target_does_not_emit_fallbacks():
    x, w = _xw()
    tr = Tracer()
    with tr.activate():
        y = conv2d_lb(x, w, padding=1, target="interpret")
    assert not [r for r in tr.records if r.name == "exec.fallback"]
    yl = conv2d_lb(x, w, padding=1, target="lax")
    assert float(jnp.max(jnp.abs(y - yl))) < 1e-4
