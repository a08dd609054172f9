"""Jit'd wrapper + host-side ELL packer for the degree-binned SpMV.

``dbg_spmv`` is the end-to-end pull-mode edge map over a DBG-reordered graph:
host-side, rows (destinations) are packed per DBG group into ELL tiles whose
width is the group's degree ceiling (geometric ranges → <= 2x padding); on
device, one ``ell_spmv_pallas`` call per group.  The per-group widths are the
paper's Table IV column structure made executable.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ...graph import csr as csr_mod
from .csr_spmv import ell_spmv_pallas
from .ref import ell_spmv_ref

__all__ = ["EllGroup", "ell_pack_groups", "dbg_spmv", "ell_spmv"]


@dataclasses.dataclass(frozen=True)
class EllGroup:
    rows: np.ndarray  # (R,) destination vertex ids (unpadded count = R_true)
    idx: np.ndarray  # (R_pad, W_pad) int32 source indices (0 for padding)
    w: np.ndarray  # (R_pad, W_pad) f32 weights (0 for padding)
    num_rows: int  # true (unpadded) row count


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def ell_pack_groups(
    g: csr_mod.Graph,
    boundaries: Sequence[int],
    *,
    row_tile: int = 256,
    width_tile: int = 512,
) -> List[EllGroup]:
    """Pack in-CSR rows into per-DBG-group ELL tiles (host-side, one pass)."""
    in_csr = g.in_csr
    deg = in_csr.degrees()
    b = np.asarray(boundaries, dtype=np.int64)
    asc = b[::-1]
    grp = (len(b) - 1) - (np.searchsorted(asc, deg, side="right") - 1)
    groups: List[EllGroup] = []
    for k in range(len(b)):
        rows = np.where(grp == k)[0]
        if rows.size == 0:
            continue
        wmax = int(deg[rows].max())
        if wmax == 0:
            continue  # zero-degree rows contribute nothing
        w_pad = _round_up(wmax, width_tile)
        r_pad = _round_up(rows.size, row_tile)
        idx = np.zeros((r_pad, w_pad), dtype=np.int32)
        wgt = np.zeros((r_pad, w_pad), dtype=np.float32)
        for i, r in enumerate(rows):  # row-major fill; vectorizable if hot
            s, e = in_csr.indptr[r], in_csr.indptr[r + 1]
            idx[i, : e - s] = in_csr.indices[s:e]
            wgt[i, : e - s] = (
                in_csr.weights[s:e] if in_csr.weights is not None else 1.0
            )
        groups.append(EllGroup(rows=rows, idx=idx, w=wgt, num_rows=rows.size))
    return groups


@partial(jax.jit, static_argnames=("row_tile", "width_tile", "interpret"))
def ell_spmv(
    x: jnp.ndarray,
    idx: jnp.ndarray,
    w: jnp.ndarray,
    *,
    row_tile: int = 256,
    width_tile: int = 512,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Single-group jit'd wrapper (shapes already tile-aligned)."""
    return ell_spmv_pallas(
        x, idx, w, row_tile=row_tile, width_tile=width_tile, interpret=interpret
    )


def dbg_spmv(
    x: jnp.ndarray,
    groups: List[EllGroup],
    num_vertices: int,
    *,
    row_tile: int = 256,
    width_tile: int = 512,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Full pull-mode edge map: scatter per-group row sums back to vertex ids.

    ``row_tile``/``width_tile`` must match the values used by
    ``ell_pack_groups`` (the packer pads every group to these multiples).
    """
    y = jnp.zeros((num_vertices,), x.dtype)
    for gr in groups:
        rt, wt = row_tile, width_tile
        ys = ell_spmv(
            x,
            jnp.asarray(gr.idx),
            jnp.asarray(gr.w),
            row_tile=rt,
            width_tile=wt,
            interpret=interpret,
        )
        y = y.at[jnp.asarray(gr.rows)].set(ys[: gr.num_rows])
    return y
