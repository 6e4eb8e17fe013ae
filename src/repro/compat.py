"""The one home of jax API names that have moved between releases.

The supported stack is jax/jaxlib 0.9.0.  ``shard_map`` lives at the jax top
level there, and its replication check is ``check_vma``.  Import from here so
the next rename is a one-file fix:

    from repro.compat import shard_map, SHARD_MAP_NO_CHECK
"""
from __future__ import annotations

import jax

shard_map = jax.shard_map
SHARD_MAP_NO_CHECK = {"check_vma": False}
