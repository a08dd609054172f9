"""GPipe-style pipeline parallelism over a ``"pipe"`` mesh axis.

``pipeline_apply`` runs S stages over M microbatches in M + S - 1 ticks via
``shard_map``: stage params are sharded along their leading (stage) dim, so
device i holds stage i; activations hop device-to-device with ``ppermute``
(the point-to-point the schedule maps onto on real interconnects).  Device 0
feeds a fresh microbatch each tick, the last device collects finished ones —
the classic fill/steady/drain schedule with (S - 1) bubble ticks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["pipeline_apply"]


def pipeline_apply(stage_fn, params, microbatches, mesh, axis: str = "pipe"):
    """Apply S pipeline stages to M microbatches.

    ``stage_fn(stage_params, h) -> h``: one stage; ``params``: pytree whose
    leaves have a leading stage dim of size S = mesh.shape[axis];
    ``microbatches``: (M, *mb_shape).  Returns (M, *mb_shape) — identical to
    applying the stages sequentially (the test's reference).
    """
    n_stages = int(mesh.shape[axis])
    n_micro = int(microbatches.shape[0])
    n_ticks = n_micro + n_stages - 1

    def ranked(p_stacked, x):
        i = jax.lax.axis_index(axis)
        # leading stage dim is 1 after sharding: this device's stage params
        p_local = jax.tree.map(lambda a: a[0], p_stacked)
        perm = [(j, (j + 1) % n_stages) for j in range(n_stages)]

        def tick(carry, t):
            h_prev, out = carry
            feed = x[jnp.clip(t, 0, n_micro - 1)]
            h_in = jnp.where(i == 0, feed, h_prev)
            y = stage_fn(p_local, h_in)
            # microbatch fed at tick f finishes on the last device at tick
            # f + S - 1, so tick t drains microbatch t - (S - 1)
            mb = t - (n_stages - 1)
            done = jnp.logical_and(i == n_stages - 1,
                                   jnp.logical_and(mb >= 0, mb < n_micro))
            slot = jnp.clip(mb, 0, n_micro - 1)
            out = out.at[slot].set(jnp.where(done, y, out[slot]))
            h_next = jax.lax.ppermute(y, axis, perm)
            return (h_next, out), None

        h0 = jnp.zeros(x.shape[1:], x.dtype)
        out0 = jnp.zeros(x.shape, x.dtype)
        (_, out), _ = jax.lax.scan(tick, (h0, out0), jnp.arange(n_ticks))
        # only the last device filled its buffer; psum replicates the result
        return jax.lax.psum(out, axis)

    fn = jax.shard_map(ranked, mesh=mesh,
                       in_specs=(P(axis), P()), out_specs=P(),
                       check_vma=False)
    return fn(params, microbatches)
