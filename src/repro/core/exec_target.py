"""First-class execution backend selection: one :class:`ExecTarget`
instead of three uncoordinated flags.

Before this module, execution mode was smeared across ad-hoc channels:
``interpret: bool`` kwargs on the kernel wrappers, ``use_kernel: bool``
on the model/serve layers, and a planner ``target: str`` legality
profile — no single switch could turn the whole stack compiled, and
every boundary re-negotiated the flags by hand (the
``self.use_kernel and bool(use_kernel)`` idiom).  An :class:`ExecTarget`
bundles all of it:

  * ``plan_target`` — the :mod:`repro.analysis.plan_check` legality
    profile plans must be verified against (``"interpret"`` or
    ``"mosaic"``);
  * ``interpret`` — the Pallas ``interpret=`` flag the kernel call
    receives (meaningful only when ``kernel``);
  * ``kernel`` — Pallas kernel vs the ``lax`` reference path;
  * ``compute`` — ``False`` is account-only serving (planning +
    ledger, no execution).

The four targets, ordered by capability (``rank``):

  ======== ============ =========== ========= ==========
  target    plan_target  interpret   kernel    compute
  ======== ============ =========== ========= ==========
  COMPILED  mosaic       False       True      True
  INTERPRET interpret    True        True      True
  LAX       interpret    —           False     True
  ACCOUNT_ONLY interpret —           False     False
  ======== ============ =========== ========= ==========

``COMPILED`` means Mosaic: ``pallas_call(interpret=False)`` compiled
for the TPU.  Requesting it where no TPU is attached raises (Pallas
refuses a non-interpret call on the CPU backend); it never runs
something else.  CPU runs use ``INTERPRET`` (the Pallas interpreter)
or ``LAX``, and the test suite compiles the Mosaic kernels ahead of
time for a described v5e.  A COMPILED request whose plan has no
mosaic-legal shape falls back per-layer to LAX with a traced
``exec.fallback`` event and a tally (``exec_fallback_counts``) —
never silently to the interpreter.

Downward-only override negotiation is centralized in :meth:`clamp`:
``server_target.clamp(request_target)`` returns the *lower-ranked* of
the two, so a lax-only or account-only server can never be upgraded by
a caller, and the circuit breaker's degradation ladder
(:meth:`ladder`) is just the downward walk COMPILED/INTERPRET -> LAX
-> ACCOUNT_ONLY.

Frozen + hashable: an ExecTarget is jit-static-safe and can key plan
and pipeline caches directly.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ExecTarget:
    """One execution backend choice, carried through every layer."""

    name: str           # canonical spelling ("compiled", "lax", ...)
    plan_target: str    # plan_check legality profile plans verify at
    interpret: bool     # pallas_call interpret= (when kernel)
    kernel: bool        # Pallas kernel vs lax reference path
    compute: bool       # False: account-only (plan + ledger, no exec)
    rank: int           # capability order; clamp() keeps the minimum

    def __str__(self) -> str:
        return self.name

    def clamp(self, other: "ExecTarget | str | None") -> "ExecTarget":
        """Downward-only override: the lower-ranked of self and
        ``other`` (``None`` keeps self).  This is the one negotiation
        every boundary uses — a request can degrade a server's target
        (kernel -> lax, compute -> account-only) but never upgrade it.
        """
        if other is None:
            return self
        other = resolve_target(other)
        return other if other.rank < self.rank else self

    def ladder(self) -> tuple["ExecTarget", ...]:
        """The circuit breaker's degradation ladder from this target:
        itself, then every strictly-lower canonical rung (LAX,
        ACCOUNT_ONLY).  ACCOUNT_ONLY's ladder is just itself."""
        return (self,) + tuple(t for t in (LAX, ACCOUNT_ONLY)
                               if t.rank < self.rank)


#: canonical targets, capability-ranked (clamp keeps the minimum rank)
ACCOUNT_ONLY = ExecTarget(name="account-only", plan_target="interpret",
                          interpret=True, kernel=False, compute=False,
                          rank=0)
LAX = ExecTarget(name="lax", plan_target="interpret",
                 interpret=True, kernel=False, compute=True, rank=1)
INTERPRET = ExecTarget(name="interpret", plan_target="interpret",
                       interpret=True, kernel=True, compute=True,
                       rank=2)
COMPILED = ExecTarget(name="compiled", plan_target="mosaic",
                      interpret=False, kernel=True, compute=True,
                      rank=3)

#: every canonical target by name (CLI choices come from these keys)
TARGETS = {t.name: t for t in (INTERPRET, COMPILED, LAX, ACCOUNT_ONLY)}

_ALIASES = {"account_only": "account-only", "account": "account-only",
            "mosaic": "compiled"}


def resolve_target(value: "ExecTarget | str | None",
                   default: ExecTarget | None = None) -> ExecTarget:
    """Normalize a target spec: an :class:`ExecTarget` passes through,
    a string resolves by name (``"account_only"``/``"account"`` and
    ``"mosaic"`` are accepted aliases), ``None`` yields ``default``
    (error when no default is given)."""
    if value is None:
        if default is None:
            raise ValueError("no execution target given and no default")
        return default
    if isinstance(value, ExecTarget):
        return value
    name = str(value).strip().lower()
    name = _ALIASES.get(name, name)
    try:
        return TARGETS[name]
    except KeyError:
        raise ValueError(
            f"unknown execution target {value!r}; expected one of "
            f"{sorted(TARGETS)}") from None


def from_flags(*, use_kernel: bool = True, compute: bool = True,
               interpret: bool = True) -> ExecTarget:
    """The legacy boolean triple as an ExecTarget — the deprecated
    ``use_kernel=``/``compute=``/``--no-kernel``-style surfaces map
    through here, so old spellings keep working while every internal
    boundary speaks ExecTarget."""
    if not compute:
        return ACCOUNT_ONLY
    if not use_kernel:
        return LAX
    return INTERPRET if interpret else COMPILED
