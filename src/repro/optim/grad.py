"""Gradient machinery: accumulation and int8-compressed cross-pod exchange.

`accumulate_grads` microbatches one global batch (compute/comm overlap: XLA
overlaps each microbatch's backward collectives with the next microbatch's
forward).  `compressed_crosspod_mean` applies the error-feedback int8
all-reduce from distributed.collectives across the "pod" axis only — the
DCN hop is the thin pipe; ICI reductions stay full-precision.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.collectives import compressed_psum

__all__ = ["accumulate_grads", "compressed_crosspod_mean", "zeros_error"]


def accumulate_grads(loss_fn: Callable, params: Any, batches: Any,
                     n_micro: int) -> tuple[jax.Array, Any, Any]:
    """Mean loss/grads over n_micro microbatches (scan -> O(1) live grads).

    batches: pytree whose leaves have a leading n_micro axis.
    """
    gfn = jax.value_and_grad(loss_fn, has_aux=True)

    def body(acc, mb):
        (loss, _aux), g = gfn(params, mb)
        return jax.tree.map(jnp.add, acc, g), loss

    zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    acc, losses = jax.lax.scan(body, zero, batches)
    grads = jax.tree.map(lambda g: g / n_micro, acc)
    return jnp.mean(losses), grads, None


def zeros_error(grads: Any) -> Any:
    return jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), grads)


def compressed_crosspod_mean(grads: Any, error: Any, mesh,
                             pod_axis: str = "pod") -> tuple[Any, Any]:
    """int8 error-feedback mean of per-pod gradients across the pod axis.

    grads must be per-pod partial means (batch sharded per pod, loss averaged
    within pod).  Leaves are exchanged compressed; error feedback carries the
    quantization residual to the next step.  ``mesh`` may be a jax Mesh or a
    ``distributed.plan.Topology`` (built into a mesh here).
    """
    from repro.distributed.plan import Topology
    if isinstance(mesh, Topology):
        mesh = mesh.build_mesh()
    n_pods = mesh.shape[pod_axis]

    def local(g, e):
        def one(gl, el):
            s, e2 = compressed_psum(gl, pod_axis, el)
            return s / n_pods, e2
        flat_g, treedef = jax.tree.flatten(g)
        out = [one(gl, el) for gl, el in zip(flat_g, jax.tree.leaves(e))]
        return (treedef.unflatten([o[0] for o in out]),
                treedef.unflatten([o[1] for o in out]))

    spec = jax.tree.map(lambda _: P(), grads)
    return jax.shard_map(local, mesh=mesh, in_specs=(spec, spec),
                         out_specs=(spec, spec), check_vma=False)(grads, error)
