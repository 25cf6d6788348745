"""GPipe-style pipeline parallelism over a mesh axis.

``pipeline_forward`` runs a stack of identical layers whose weights are
sharded one-stage-per-device over ``axis``, streaming microbatches through
the ring: at step t, stage 0 ingests microbatch t while stage s processes
the activation it received from stage s-1, and every stage forwards its
output with one ``ppermute``. After ``n_microbatches + n_stages - 1`` steps
every microbatch has crossed every stage — the classic pipeline fill/drain
schedule, expressed as a ``fori_loop`` inside one ``shard_map``.

This is the third decomposition the scaling story needs next to the row
sharding of ``repro.dist`` (data/plan parallel) and the expert parallelism
in ``models/moe.py``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.compat import shard_map


def pipeline_forward(layer, weights: jax.Array, x: jax.Array, mesh,
                     axis: str = "pipe") -> jax.Array:
    """Apply ``n_stages`` layers to microbatched ``x`` through the pipeline.

    layer:    ``(w, h) -> h`` — one stage's computation.
    weights:  (n_stages, ...) stage weights, sharded over ``axis``.
    x:        (n_microbatches, ...) microbatches, replicated.
    Returns the replicated (n_microbatches, ...) outputs, equal to applying
    the stages serially.
    """
    n_stages = mesh.shape[axis]
    n_mb = x.shape[0]
    ring = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def fn(w_loc, x_all):
        w = w_loc[0]
        idx = jax.lax.axis_index(axis)

        def step(t, carry):
            buf, outs = carry
            inp = jnp.where(idx == 0, x_all[jnp.clip(t, 0, n_mb - 1)], buf)
            out = layer(w, inp)
            mb = t - (n_stages - 1)  # microbatch draining at the last stage
            write = (idx == n_stages - 1) & (mb >= 0)
            slot = jnp.clip(mb, 0, n_mb - 1)
            outs = outs.at[slot].set(jnp.where(write, out, outs[slot]))
            buf = jax.lax.ppermute(out, axis, ring)
            return buf, outs

        # the carry turns device-varying after the first ppermute, so it
        # starts varying over the pipe axis too
        carry0 = jax.lax.pcast(
            (jnp.zeros_like(x_all[0]), jnp.zeros_like(x_all)), (axis,),
            to="varying")
        _, outs = jax.lax.fori_loop(0, n_mb + n_stages - 1, step, carry0)
        # results live on the last stage only; psum replicates them
        return jax.lax.psum(
            jnp.where(idx == n_stages - 1, outs, jnp.zeros_like(outs)), axis)

    return shard_map(
        fn, mesh=mesh, in_specs=(P(axis), P()), out_specs=P(),
    )(weights, x)
