"""GPipe-style pipeline parallelism over a mesh axis (shard_map).

For deployments that prefer pod-level PP over pure DP across pods
(DESIGN.md §5): the layer stack is split into ``n_stages`` contiguous
stages, microbatches stream through with ``collective_permute`` hops, and
the bubble is the standard (S-1)/(M+S-1) GPipe bubble.

This is the *collective pattern* proof (tested on a host mesh); wiring it
to the full LM stack is a config choice (`pod` axis as the stage axis).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(stage_fn, n_stages: int, n_micro: int, axis: str = "pipe"):
    """Build a pipelined forward: ``f(stage_params, x) -> y``.

    ``stage_params``: leaves with leading dim ``n_stages`` (sharded over
    ``axis``); ``x``: (n_micro, micro_batch, ...) activations entering
    stage 0.  Inside shard_map each device holds ONE stage's params and
    runs the classic skewed schedule: at tick t it processes microbatch
    ``t - stage`` (when in range) and permutes its output to stage+1.
    """

    def per_stage(params, x):
        # params: (1, ...) local slice -> squeeze; x: (n_micro, mb, ...)
        params = jax.tree.map(lambda a: a[0], params)
        stage = jax.lax.axis_index(axis)
        mb = x[0]
        buf = jnp.zeros_like(mb)                 # activation in flight
        outs = jnp.zeros_like(x)

        def tick(carry, t):
            buf, outs = carry
            # stage 0 injects microbatch t from its local input
            inject = jnp.where(t < x.shape[0], t, 0)
            buf = jnp.where(stage == 0, x[inject], buf)
            m_idx = t - stage                     # microbatch at this stage
            active = (m_idx >= 0) & (m_idx < x.shape[0])
            y = stage_fn(params, buf)
            y = jnp.where(active, y, buf)
            # last stage collects its finished microbatch
            outs = jax.lax.cond(
                active & (stage == n_stages - 1),
                lambda o: o.at[jnp.clip(m_idx, 0, x.shape[0] - 1)].set(y),
                lambda o: o, outs)
            # ring-shift activations to the next stage
            buf = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (buf, outs), None

        ticks = jnp.arange(n_micro + n_stages - 1)
        (buf, outs), _ = jax.lax.scan(tick, (buf, outs), ticks)
        # every device returns outs; only the last stage's is meaningful --
        # psum so the result is replicated (cheap at toy scale; a real
        # deployment would leave it stage-local)
        outs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs)),
            axis)
        return outs

    def run(mesh: Mesh, stage_params, x):
        f = jax.shard_map(
            per_stage, mesh=mesh,
            in_specs=(P(axis), P()),
            out_specs=P(),
            check_vma=False)
        return f(stage_params, x)

    return run
