"""The repo's one spelling of ``shard_map`` and ``axis_size``.

Sharded models import both from here (lint rule L001), so an API move
in a later JAX is one edit, not one per model.
"""

from __future__ import annotations

import jax

shard_map = jax.shard_map
axis_size = jax.lax.axis_size
