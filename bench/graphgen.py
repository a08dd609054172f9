"""GAP's ``kron`` and ``urand`` graphs (Graph500 Kronecker draws), on the chip.

One jitted call turns a seed into the graph a configuration describes, as
the GAP Benchmark Suite's generator and builder make it:

1. ``edge_factor`` x V endpoint pairs are drawn, each vertex id built bit by
   bit: per bit one uniform number picks the quadrant [A | B / C | D] (the
   Graph500 specification's recursive matrix; A = B = C = 0.25 is GAP's
   uniform ``urand``).
2. The graph is symmetrized, as GAP's are: every pair stands for an
   undirected edge, so both of its arcs are kept, and every vertex's
   in-degree equals its out-degree.
3. Self-loops and repeated arcs are dropped, as GAP's builder does; the
   number of arcs left is what the draws give, not a target.
4. Vertex ids are relabelled by a random permutation, as Graph500 and GAP
   do, so the original order carries no degree information.

The draws come from the configuration's fixed ``graph_seed``: one edge set
per configuration, as a benchmark's data set is one file.  The relabelling
comes from the run's ``--seed``, so every seed hands the program another
labelling of the same graph: the same work (iterations, rounds, layout
shapes and so compiled programs) in another vertex order.  The program
under test receives only the relabelled (src, dst) arc arrays.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["seed_key", "kronecker_arcs", "generate"]


def seed_key(seed: int):
    """A threefry key from any non-negative seed below 2**64 (two 32-bit
    words, so seeds past 2**31 work with 32-bit JAX)."""
    if not 0 <= seed < 1 << 64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    words = np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)
    return jax.random.wrap_key_data(words, impl="threefry2x32")


@partial(jax.jit, static_argnames=("scale", "draws", "abc"))
def kronecker_arcs(k_bits, k_perm, *, scale: int, draws: int, abc: tuple):
    """Returns ``(src, dst, perm, arcs)``: the distinct non-loop arcs of
    ``draws`` Kronecker pairs from ``k_bits`` and of their reverses,
    relabelled by ``perm`` (drawn from ``k_perm``; ``perm[c]`` is the id of
    drawn vertex ``c``), in the first ``arcs`` places of the two arrays of
    ``2 * draws``."""
    a, b, c = abc
    v = 1 << scale

    def bit(i, ids):
        src, dst = ids
        u = jax.random.uniform(jax.random.fold_in(k_bits, i), (draws,))
        down = u >= a + b
        right = ((u >= a) & (u < a + b)) | (u >= a + b + c)
        return (src * 2 + down.astype(jnp.int32),
                dst * 2 + right.astype(jnp.int32))

    zeros = jnp.zeros((draws,), jnp.int32)
    src, dst = jax.lax.fori_loop(0, scale, bit, (zeros, zeros))
    src, dst = jnp.concatenate([src, dst]), jnp.concatenate([dst, src])
    src, dst = jax.lax.sort((src, dst), num_keys=2)
    first = jnp.concatenate([jnp.ones((1,), bool),
                             (src[1:] != src[:-1]) | (dst[1:] != dst[:-1])])
    keep = first & (src != dst)
    # the dropped arcs move past the kept ones, which stay sorted
    src, dst = jax.lax.sort((jnp.where(keep, src, v), dst), num_keys=2)
    perm = jax.random.permutation(k_perm, v).astype(jnp.int32)
    return (perm[jnp.minimum(src, v - 1)], perm[dst], perm,
            jnp.sum(keep))


def generate(cfg: dict, seed: int):
    """Host int32 arrays ``(src, dst, perm)`` for configuration ``cfg``:
    its graph's arcs relabelled by the permutation that ``seed`` draws."""
    if cfg.get("symmetric") is not True:
        raise ValueError("the generator builds symmetrized graphs only; "
                         "the configuration has to say 'symmetric': true")
    scale = cfg["scale"]
    src, dst, perm, arcs = kronecker_arcs(
        seed_key(cfg["graph_seed"]), seed_key(seed), scale=scale,
        draws=cfg["edge_factor"] << scale,
        abc=tuple(float(x) for x in cfg["abc"]))
    arcs = int(arcs)
    return (np.asarray(src)[:arcs], np.asarray(dst)[:arcs],
            np.asarray(perm))
