"""Static-analysis gate rows: the plan audit over every committed
graph plus the standing-policy lint, published as diff_bench-gated
metrics.

``plan_audit_legal_frac`` must stay 1.0 (every fwd/dgrad/wgrad plan of
``vgg_graph`` + ``resnet_graph`` legal at the paper's 1 MiB accounting
budget), ``plan_audit_traffic_mismatches`` and ``lint_errors`` must
stay 0 — a planner, accountant, or policy regression fails the gate
before it can skew any traffic ratio.
"""

from __future__ import annotations

import time

MB = 1024 * 1024


def bench_plan_audit():
    """Audit every vgg/resnet node (fwd+dgrad+wgrad) at 1 MiB: the
    interpret-profile (structural) legality fraction and the symbolic
    traffic/bound cross-audit, plus the mosaic-profile legality
    fraction of the plans a ``target="compiled"`` run executes (planned
    at the mosaic profile and the scoped VMEM limit)."""
    import jax

    from repro.analysis.plan_check import TARGET_MOSAIC, audit_graph
    from repro.core.tpu_adapter import VMEM_LIMIT_BYTES
    from repro.models.cnn import init_vgg, resnet_graph, vgg_graph

    graphs = [(vgg_graph(init_vgg(jax.random.PRNGKey(0))), 224),
              (resnet_graph(), 32)]
    rows = []
    n_legal = n_plans = mismatches = 0
    t0 = time.perf_counter()
    for graph, hw in graphs:
        a = audit_graph(graph, hw, hw, batch=8, vmem_budget=MB,
                        training=True)
        n_legal += a.n_legal
        n_plans += a.n_plans
        mismatches += a.traffic_mismatches + a.bound_mismatches
    us = (time.perf_counter() - t0) * 1e6 / max(1, n_plans)
    rows.append(("audit/vgg+resnet/plan_audit_legal_frac", us,
                 round(n_legal / max(1, n_plans), 4)))
    rows.append(("audit/vgg+resnet/plan_audit_traffic_mismatches", None,
                 mismatches))
    rows.append(("audit/vgg+resnet/plans_checked", None, n_plans))

    # the compiled path's own plans (fwd+dgrad+wgrad) under the
    # mosaic profile at the execution budget
    m_legal = m_plans = 0
    for graph, hw in graphs:
        a = audit_graph(graph, hw, hw, batch=8,
                        vmem_budget=VMEM_LIMIT_BYTES, training=True,
                        target=TARGET_MOSAIC)
        m_legal += a.n_legal
        m_plans += a.n_plans
    rows.append(("audit/vgg+resnet/mosaic_exec_legal_frac", None,
                 round(m_legal / max(1, m_plans), 4)))
    return rows


def bench_lint():
    """The standing-policy lint over the whole repo; the gate is that
    the error count stays 0."""
    from repro.analysis.lint import lint_repo

    t0 = time.perf_counter()
    findings = lint_repo()
    us = (time.perf_counter() - t0) * 1e6
    return [("audit/repo/lint_errors", us, len(findings))]


ALL_AUDIT = [bench_plan_audit, bench_lint]
