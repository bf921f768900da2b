"""``shard_map`` plumbing shared by core and models.

Every ``shard_map`` call site in this repo takes the function and its
replication-check switch from here, so the choice lives in one place
(core never imports from models for it).
"""

from __future__ import annotations

import jax
from jax import shard_map

SM_CHECK_KW = {"check_vma": False}


def axis_size(axis) -> int:
    """Static size of a named mesh axis (or tuple of axes) inside shard_map."""
    return jax.lax.axis_size(axis)


__all__ = ["shard_map", "SM_CHECK_KW", "axis_size"]
