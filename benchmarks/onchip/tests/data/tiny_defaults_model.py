"""Test data: a configuration's model module that brings the default
parts under their own names, so that a run through it must read
exactly what a run without it reads."""

from harness.model import check_graph, init_params  # noqa: F401
from harness.reference import logits, loss  # noqa: F401
from harness.work import forward_flops, pass_work, train_flops  # noqa: F401
