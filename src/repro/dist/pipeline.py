"""Collective (GPipe-schedule) pipeline parallelism over one mesh axis.

Stage ``i``'s parameters live on mesh slice ``i`` of ``axis``; microbatches
stream through the pipe with a ``ppermute`` ring shift per tick.  With
``S`` stages and ``M`` microbatches the schedule runs ``M + S - 1`` ticks:
tick ``t`` has stage 0 ingesting microbatch ``t`` while stage ``S-1``
retires microbatch ``t - (S-1)`` -- the standard fill/drain bubble of
``(S-1)/(M+S-1)``.

Only forward is implemented (enough for the serving/eval path and the
dry-run's schedule validation); training pipelines stack this with
per-stage grad accumulation.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_forward(stage_fn, mesh, axis: str, stage_params, xs):
    """Run ``xs`` through ``S`` stages placed along ``axis``.

    stage_fn: ``(W_i, x) -> y`` applied by stage i.
    stage_params: [S, ...] stacked per-stage parameters (S == mesh[axis]).
    xs: [M, ...] microbatches, replicated.
    Returns [M, ...]: ``stage_{S-1}(... stage_0(xs[m]) ...)`` per m.
    """
    num_stages = mesh.shape[axis]
    num_micro = xs.shape[0]
    if stage_params.shape[0] != num_stages:
        raise ValueError(
            f"{stage_params.shape[0]} stages vs mesh axis "
            f"{axis}={num_stages}")

    def run(w_local, xs_full):
        w = w_local[0]                       # this shard's stage params
        idx = jax.lax.axis_index(axis)
        last = num_stages - 1
        acts = jnp.zeros_like(xs_full[0])
        outs = jnp.zeros_like(xs_full)

        def tick(carry, t):
            acts, outs = carry
            feed = xs_full[jnp.minimum(t, num_micro - 1)]
            acts = jnp.where((idx == 0) & (t < num_micro), feed, acts)
            y = stage_fn(w, acts)
            m = t - last                    # microbatch retiring this tick
            done = (idx == last) & (m >= 0)
            outs = outs.at[jnp.clip(m, 0, num_micro - 1)].add(
                jnp.where(done, y, 0))
            # shift activations one stage down the pipe
            nxt = jax.lax.ppermute(
                y, axis, [(i, i + 1) for i in range(last)])
            acts = jnp.where(idx == 0, acts, nxt)
            return (acts, outs), None

        (_, outs), _ = jax.lax.scan(
            tick, (acts, outs), jnp.arange(num_micro + last))
        # only the last stage holds real outputs; broadcast them
        return jax.lax.psum(jnp.where(idx == last, outs, 0), axis)

    fn = jax.shard_map(run, mesh=mesh, in_specs=(P(axis), P()),
                       out_specs=P(), check_vma=False)
    return fn(stage_params, xs)
