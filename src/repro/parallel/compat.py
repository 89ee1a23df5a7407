"""The repo's one spelling of ``shard_map`` and ``axis_size``.

Sharded code imports both from here (lint rule L001), so an API move
in a later JAX is one edit, not one per module.  It is kept for the
four-chip spatial-halo conv, whose ``ppermute`` halo exchange runs
inside a ``shard_map``.
"""

from __future__ import annotations

import jax

shard_map = jax.shard_map
axis_size = jax.lax.axis_size
