"""Jit'd wrapper for the DBG binning kernel: padding + stable rank assembly.

``dbg_bin`` produces everything Listing 1 needs: group ids, histogram, and the
final stable mapping (step 3) — the rank-within-group is a cumulative count,
computed with one exclusive scan over the one-hot group matrix (XLA), since
the cross-tile scan carries a sequential dependency that belongs to the outer
program, not the tile kernel.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from .hist_bin import hist_bin_pallas
from .ref import assign_bins_ref

__all__ = ["dbg_bin", "stable_mapping_from_groups"]


def _pad_to(x: jnp.ndarray, multiple: int, fill) -> jnp.ndarray:
    n = x.shape[0]
    rem = (-n) % multiple
    if rem == 0:
        return x
    return jnp.concatenate([x, jnp.full((rem,), fill, x.dtype)])


def stable_mapping_from_groups(groups: jnp.ndarray, num_groups: int) -> jnp.ndarray:
    """Listing 1 step 3: new id = (start of my group) + (my stable rank within
    group).  Stable rank via exclusive cumsum of the one-hot group matrix."""
    onehot = (groups[:, None] == jnp.arange(num_groups, dtype=groups.dtype)[None, :])
    onehot = onehot.astype(jnp.int32)
    within = jnp.cumsum(onehot, axis=0) - onehot  # exclusive: count of earlier same-group
    sizes = jnp.sum(onehot, axis=0)
    starts = jnp.cumsum(sizes) - sizes
    return starts[groups] + jnp.take_along_axis(within, groups[:, None], axis=1)[:, 0]


@partial(jax.jit, static_argnames=("tile", "interpret"))
def dbg_bin(
    degrees: jnp.ndarray,
    boundaries: jnp.ndarray,
    *,
    tile: int = 4096,
    interpret: Optional[bool] = None,
):
    """Full DBG (Listing 1) on device. Returns (mapping, groups, histogram)."""
    v = degrees.shape[0]
    # pad with degree 0 → padding lands in the LAST (coldest) group, whose
    # histogram count is corrected below
    deg_p = _pad_to(degrees.astype(jnp.int32), tile, jnp.int32(0))
    use_pallas = deg_p.shape[0] % tile == 0
    if use_pallas:
        groups_p, hist = hist_bin_pallas(
            deg_p, boundaries.astype(jnp.int32), tile=tile, interpret=interpret
        )
    else:  # pragma: no cover — padding guarantees divisibility
        groups_p = assign_bins_ref(deg_p, boundaries)
        hist = jnp.zeros((boundaries.shape[0],), jnp.int32).at[groups_p].add(1)
    groups = groups_p[:v]
    # remove padding's contribution to the histogram (padding deg=-1 -> last group)
    pad = deg_p.shape[0] - v
    hist = hist.at[boundaries.shape[0] - 1].add(-pad)
    mapping = stable_mapping_from_groups(groups, boundaries.shape[0])
    return mapping, groups, hist
