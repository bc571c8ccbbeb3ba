"""Pipeline parallelism over the "pod" axis (GPipe schedule via shard_map).

The multi-pod mesh (pod=2, data=16, model=16) can run the pod axis as DP
(default) or as a 2-stage pipeline: each pod holds half the layer groups;
activations flow pod0 -> pod1 through `ppermute` (DCN), microbatched so the
bubble is 1/(M+1).  Implemented generically for S stages / M microbatches;
autodiff works through ppermute (its transpose is the reverse permute), so
the same schedule serves training.

This is a *feature module*: launch/train.py enables it with --pp, and
tests/test_pipeline.py checks S-stage == single-device numerics.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, PartitionSpec as P

__all__ = ["pipeline_apply", "split_stages"]


def split_stages(seq: tuple, n_stages: int) -> tuple:
    """Split a tuple of layer-params into n_stages contiguous chunks."""
    n = len(seq)
    per = (n + n_stages - 1) // n_stages
    return tuple(seq[i * per:(i + 1) * per] for i in range(n_stages))


def pipeline_apply(stage_fn: Callable, stage_params, x: jax.Array, *,
                   mesh, axis: str = "pod", n_microbatches: int = 4):
    """Run x through S pipeline stages sharded on `axis`.

    stage_fn(params_for_stage, x_mb) -> y_mb, applied per microbatch.
    stage_params: pytree whose leaves have a leading S axis (stage-stacked).
    x: (B, ...) with B divisible by n_microbatches.

    Returns y with the same shape as x.  GPipe schedule: T = M + S - 1 ticks;
    at each tick every stage processes one in-flight microbatch and the
    boundary activation hops stages via ppermute.
    """
    n_stages = mesh.shape[axis]
    m = n_microbatches
    b = x.shape[0]
    if b % m:
        raise ValueError(f"batch {b} not divisible by microbatches {m}")

    def local(params_local, x_local):
        # params_local: this stage's params — shard_map keeps the sharded
        # stage axis with local extent 1; squeeze it off
        params_local = jax.tree.map(lambda t: t[0], params_local)
        # x_local: full batch (replicated across the pod axis)
        sid = jax.lax.axis_index(axis)
        mbs = x_local.reshape((m, b // m) + x_local.shape[1:])
        ticks = m + n_stages - 1
        zero = jnp.zeros_like(mbs[0])
        carry_in = zero        # activation arriving from the previous stage
        outs = jnp.zeros_like(mbs)

        def tick(t, state):
            carry_in, outs = state
            # stage 0 injects microbatch t (when in range); others consume
            mb_idx = jnp.clip(t, 0, m - 1)
            inject = jnp.where(t < m, 1.0, 0.0)
            x_in = jnp.where(sid == 0, mbs[mb_idx] * inject, carry_in)
            y = stage_fn(params_local, x_in)
            # pass to next stage; last stage's output is collected
            carry_out = jax.lax.ppermute(
                y, axis, [(i, i + 1) for i in range(n_stages - 1)])
            done_idx = jnp.clip(t - (n_stages - 1), 0, m - 1)
            take = (sid == n_stages - 1) & (t >= n_stages - 1)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(take, y, outs[done_idx]), done_idx, 0)
            return carry_out, outs

        carry_in, outs = jax.lax.fori_loop(0, ticks, tick, (carry_in, outs))
        # broadcast final outputs from the last stage to all pods
        outs = jax.lax.psum(jnp.where(sid == n_stages - 1, outs, 0.0), axis)
        return outs.reshape(x_local.shape)

    pspec = jax.tree.map(lambda _: P(axis), stage_params)
    # the schedule is manual SPMD: run it on Auto-typed axes of the same
    # devices, so that autodiff's transpose of it needs no ambient mesh
    # context even when the caller's mesh has Explicit axes
    mesh = Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))
    return jax.shard_map(local, mesh=mesh, in_specs=(pspec, P()),
                         out_specs=P(), check_vma=False)(stage_params, x)
