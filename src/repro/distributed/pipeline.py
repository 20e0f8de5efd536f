"""Optional pipeline parallelism over the 'pod' axis (GPipe schedule).

The default multi-pod posture treats 'pod' as DP (lower collective volume at
2 pods — EXPERIMENTS.md §Perf); this module provides the alternative: layer
stages sharded over 'pod', microbatches streamed with collective_permute, for
topologies where cross-pod all-reduce is the bottleneck.

Implementation: shard_map over the stage axis. Stage s holds stacked
super-block params slice s. The classic GPipe loop runs n_micro + n_stages-1
ticks; at each tick a stage processes the activation it received last tick
and ppermutes its output to stage s+1. Bubbles are masked compute.

Compile-checked in the multi-pod dry-run (--pipeline); numerically validated
against the unpipelined model on a 1-stage degenerate mesh in tests and on
4 fake devices in the dry-run harness.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def pipeline_forward(stage_fn, n_stages: int, axis: str = "pod"):
    """Build fn(stage_params, x_micro) -> y_micro running under shard_map.

    stage_params: pytree with leading stage axis (sharded over ``axis``).
    x_micro: [n_micro, Bm, S, D] microbatched activations (replicated).
    stage_fn(params_slice, x) -> y, applied by every stage to its slice.
    """

    def run(stage_params, x_micro):
        stage_id = jax.lax.axis_index(axis)
        n_micro = x_micro.shape[0]
        ticks = n_micro + n_stages - 1
        buf = jnp.zeros_like(x_micro[0])
        outs = jnp.zeros_like(x_micro)

        p_local = jax.tree_util.tree_map(lambda a: a[0], stage_params)

        def tick(t, carry):
            buf, outs = carry
            # stage 0 ingests microbatch t (when in range)
            feed = jnp.where(t < n_micro, 1, 0)
            x_in = jnp.where(
                (stage_id == 0) & (feed == 1),
                x_micro[jnp.minimum(t, n_micro - 1)], buf)
            y = stage_fn(p_local, x_in)
            # last stage emits microbatch t - (n_stages - 1)
            out_idx = t - (n_stages - 1)
            emit = (stage_id == n_stages - 1) & (out_idx >= 0)
            outs = jax.lax.cond(
                emit,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.maximum(out_idx, 0), 0),
                lambda o: o, outs)
            # pass activations downstream (ring permute; stage 0 receives
            # garbage from the last stage and overwrites it on ingest)
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            buf = jax.lax.ppermute(y, axis, perm)
            return buf, outs

        _, outs = jax.lax.fori_loop(0, ticks, tick, (buf, outs))
        # only the last stage holds real outputs; broadcast them
        outs = jax.lax.psum(
            jnp.where(stage_id == n_stages - 1, outs, jnp.zeros_like(outs)),
            axis)
        return outs

    return run


def pipelined_apply(mesh: Mesh, stage_fn, stage_params, x_micro,
                    axis: str = "pod"):
    """shard_map wrapper; stage_params leading dim == mesh axis size."""
    n_stages = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    run = pipeline_forward(stage_fn, n_stages, axis)
    spec_params = jax.tree_util.tree_map(lambda _: P(axis), stage_params)
    fn = jax.shard_map(
        run, mesh=mesh,
        in_specs=(spec_params, P()),
        out_specs=P(),
        check_vma=False)
    return fn(stage_params, x_micro)
