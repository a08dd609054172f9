"""The main path's Pallas kernels compile for a TPU v5e, at Graph500 scale-22
tile shapes, with no chip attached.

Each test lowers and compiles for a described (not attached) ``v5e:2x2``
topology and asserts the compiled HLO holds the Mosaic kernel
(``tpu_custom_call``): what Mosaic refuses here (rank-1 blocks, in-kernel
gathers, int8 compares) is caught without chip time.  Nothing runs, so
nothing here checks a result — the interpret-mode tests do that.  The
topology is described inside a fixture (never at import), and every test of
this file lives in this one file so a single xdist worker loads the TPU
compiler.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.dist import graph as dg
from repro.kernels.edge_map.edge_map import ell_edge_map_pallas
from repro.kernels.edge_map.ops import EllTileGroup, fused_edge_map

V = 1 << 22  # Graph500 scale 22


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _tiles(geometry, sharding, id_dtype=jnp.int32):
    return tuple(EllTileGroup(rows=_sds((r,), jnp.int32, sharding),
                              idx=_sds((r, w), id_dtype, sharding),
                              deg=_sds((r,), jnp.int32, sharding))
                 for r, w in geometry)


def _compiled_has_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    return "tpu_custom_call" in text


def test_ell_sum_pull_compiles(one_chip):
    """PageRank's pull: the hottest width class and the largest cold one."""
    tiles = _tiles([(32, 16768), (1446144, 8)], one_chip)

    def pull(tiles, x):
        return fused_edge_map(tiles, x, V, interpret=False)

    assert _compiled_has_kernel(pull, tiles, _sds((V,), jnp.float32,
                                                  one_chip))


@pytest.mark.parametrize("rows,width", [(500032, 24), (1602560, 40),
                                        (320, 104)])
def test_histogram_width_class_compiles(one_chip, rows, width):
    """Width classes chosen from the degree histogram take any multiple of
    8 below one width tile: scale-22 urand's largest class is w40."""
    tiles = _tiles([(rows, width)], one_chip)

    def pull(tiles, x):
        return fused_edge_map(tiles, x, V, interpret=False)

    assert _compiled_has_kernel(pull, tiles, _sds((V,), jnp.float32,
                                                  one_chip))


def test_ell_min_frontier_compiles(one_chip):
    """SSSP's push: min over unit weights, source frontier, init rows."""
    tiles = _tiles([(281344, 32)], one_chip)

    def push(tiles, dist, frontier):
        return fused_edge_map(tiles, dist, V, reduce="min",
                              src_frontier=frontier, use_weights=True,
                              neutral=float("inf"), init=dist,
                              interpret=False)

    assert _compiled_has_kernel(push, tiles,
                                _sds((V,), jnp.float32, one_chip),
                                _sds((V,), jnp.bool_, one_chip))


def test_planar_pull_compiles(one_chip):
    """Serving's (V, 8) plane: K=8 queries through one pass."""
    tiles = _tiles([(232960, 16)], one_chip)

    def pull(tiles, plane):
        return fused_edge_map(tiles, plane, V, interpret=False)

    assert _compiled_has_kernel(pull, tiles,
                                _sds((V, 8), jnp.float32, one_chip))


def test_uint16_hot_table_compiles(one_chip):
    """A packed hot slot table stored with uint16 ids, with tombstones."""

    def hot(x, idx, deg, alive):
        return ell_edge_map_pallas(x, idx, deg, alive=alive, row_tile=64,
                                   width_tile=128, interpret=False)

    r, w = 4096, 2048
    assert _compiled_has_kernel(
        hot, _sds((1 << 16,), jnp.float32, one_chip),
        _sds((r, w), jnp.uint16, one_chip),
        _sds((r,), jnp.int32, one_chip),
        _sds((r, w), jnp.int8, one_chip))


def test_sharded_ell_pull_compiles(topo):
    """The sharded engine's fused pull over a four-chip mesh: halo
    all_to_all, then one kernel per width class on each shard."""
    d, v_blk, hot_cap, halo = 4, V // 4, 200_000, 400_000
    mesh = Mesh(np.array(topo.devices), (dg.AXIS,))
    split = NamedSharding(mesh, P(dg.AXIS))
    rep = NamedSharding(mesh, P())
    tiles = tuple(EllTileGroup(rows=_sds((d, r), jnp.int32, split),
                               idx=_sds((d, r, w), jnp.int32, split),
                               deg=_sds((d, r), jnp.int32, split))
                  for r, w in [(8, 40960), (361536, 8)])
    small = _sds((d, 8), jnp.int32, split)
    sg0 = dg.ShardedGraphArrays(
        n_shards=d, num_vertices=V, v_blk=v_blk, halo_max=halo,
        policy="replicate_hot", in_slot=small, in_dst_local=small,
        in_w=small, in_mask=small,
        send_idx=_sds((d, d, halo), jnp.int32, split),
        hot_ids=_sds((hot_cap,), jnp.int32, rep),
        out_src_local=small, out_dst=small, out_w=small, out_mask=small,
        in_deg=_sds((V,), jnp.int32, rep), out_deg=_sds((V,), jnp.int32, rep),
        backend="ell", hot_cap=hot_cap, interpret=False, pull_tiles=tiles)

    def pull(prop, send_idx, hot_ids, tiles):
        sg = dataclasses.replace(sg0, send_idx=send_idx, hot_ids=hot_ids,
                                 pull_tiles=tiles)
        return dg.edge_map_pull_sharded(sg, prop, mesh)

    assert _compiled_has_kernel(pull, _sds((V,), jnp.float32, rep),
                                sg0.send_idx, sg0.hot_ids, tiles)


def test_kernel_and_gather_carry_their_names(one_chip):
    """On the TPU the kernel's instruction is named ``ell_edge_map`` and the
    fusion that gathers ``x[idx]`` carries the ``edge_map.gather`` scope
    (under its width group's ``ell.w<W>``) in its metadata: what the
    benchmark's trace readers key on."""
    tiles = _tiles([(64, 256), (4096, 8)], one_chip)

    def pull(tiles, x):
        return fused_edge_map(tiles, x, V, interpret=False)

    text = jax.jit(pull).lower(
        tiles, _sds((V,), jnp.float32, one_chip)).compile().as_text()
    ops = re.findall(r'%([\w.-]+) = (\S+) ([\w-]+)\(.*?'
                     r'metadata=\{[^}]*?op_name="([^"]*)"', text)
    kernels = [n for n, _, code, _ in ops if code == "custom-call"
               and n.startswith("ell_edge_map")]
    assert len(kernels) == 2
    for w in (256, 8):
        gathers = [n for n, _, code, p in ops if code == "fusion"
                   and p.endswith(f"ell.w{w}/edge_map.gather/gather")]
        assert gathers, w
