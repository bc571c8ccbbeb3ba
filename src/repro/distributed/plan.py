"""Topology + ShardingPlan: the one-stop distributed layout API.

``distributed/`` grew as a bag of helpers (``param_specs``, ``zero1_specs``,
``batch_spec``, ``make_production_mesh``, ``dp_axes_for``) that training
could stitch together but serving could not consume.  This module
consolidates them into two frozen objects:

  * ``Topology`` — the logical mesh: (pods, dp, tp) extents, axis names,
    predicate helpers (``model_divides``, ``dp_axes_for``), mesh
    construction with an actionable error when the host is short on
    devices, and ``shrink()`` for elastic recovery after device loss.
  * ``ShardingPlan`` — param + cache + batch PartitionSpecs resolved once
    per config/tree, validated against the actual pytree (every sharded
    dim must divide by its axis extent), convertible to ``NamedSharding``
    trees for explicit jit in/out shardings, and reprintable
    (``describe()``) for debugging.

TWD base-3 packed slabs inherit their master weight's spec (see
``distributed/sharding.py``'s K-packing note): an N-dim "model" shard never
splits a packed byte, and the packed K dim is 16-row aligned so a K shard
stays byte-aligned for any tp <= 16.

The legacy helpers remain as warn-once ``DeprecationWarning`` shims in
``sharding.py`` / ``launch/mesh.py``; new code goes through
``ShardingPlan.for_config(cfg)`` / ``Topology``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import numpy as np
from jax.sharding import AxisType, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.distributed import sharding as _rules

__all__ = ["Topology", "ShardingPlan", "device_shortfall_hint"]


def device_shortfall_hint(need: int) -> str:
    """How to get `need` devices on this backend: the virtual-device flag on
    the CPU, the host's real chip count on an accelerator."""
    devs = jax.devices()
    if devs[0].platform == "cpu":
        return (f"relaunch with XLA_FLAGS="
                f"--xla_force_host_platform_device_count={need} "
                f"(must be set before jax initializes)")
    return (f"this host has {len(devs)} {devs[0].device_kind} "
            f"chip{'s' if len(devs) != 1 else ''}")


# -------------------------------------------------------------------------
# Topology
# -------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Topology:
    """Logical device mesh: ``dp`` data-parallel x ``tp`` tensor-parallel
    ways, optionally replicated over ``pods``.  Frozen and hashable so it
    can ride inside ``ServeConfig`` and jit closure state."""

    dp: int = 1
    tp: int = 1
    pods: int = 1

    def __post_init__(self):
        for name in ("dp", "tp", "pods"):
            v = getattr(self, name)
            if not isinstance(v, int) or v < 1:
                raise ValueError(f"Topology.{name} must be an int >= 1, "
                                 f"got {v!r}")

    # -- shape/axes --------------------------------------------------------

    @property
    def axis_names(self) -> tuple[str, ...]:
        return (("pod", "data", "model") if self.pods > 1
                else ("data", "model"))

    @property
    def shape(self) -> tuple[int, ...]:
        return ((self.pods, self.dp, self.tp) if self.pods > 1
                else (self.dp, self.tp))

    @property
    def n_devices(self) -> int:
        return self.pods * self.dp * self.tp

    @property
    def dp_axes(self) -> tuple[str, ...]:
        return ("pod", "data") if self.pods > 1 else ("data",)

    @property
    def dp_extent(self) -> int:
        return self.pods * self.dp

    def axis_size(self, axis: str) -> int:
        return {"pod": self.pods, "data": self.dp, "model": self.tp}[axis]

    # -- predicates --------------------------------------------------------

    def model_divides(self, dim: int) -> bool:
        """Can `dim` be split over the model axis?"""
        return dim > 0 and dim % self.tp == 0

    def dp_axes_for(self, global_batch: int) -> tuple[str, ...]:
        """Data-parallel axes usable for this batch (batch 1 => replicate).
        Accumulates pod then data while the batch stays divisible — the
        same contract as the legacy ``launch.mesh.dp_axes_for``."""
        dp = 1
        out = []
        for a in self.dp_axes:
            if global_batch % (dp * self.axis_size(a)) == 0:
                out.append(a)
                dp *= self.axis_size(a)
        return tuple(out)

    def batch_spec(self, *, sequence_sharded: bool = False) -> P:
        if sequence_sharded:
            return P(None, self.dp_axes)
        return P(self.dp_axes)

    # -- mesh construction -------------------------------------------------

    def build_mesh(self, devices=None):
        devs = tuple(jax.devices() if devices is None else devices)
        if len(devs) < self.n_devices:
            raise RuntimeError(
                f"Topology{self.shape} needs {self.n_devices} devices, have "
                f"{len(devs)} — {device_shortfall_hint(self.n_devices)} "
                f"or shrink --tp/--dp")
        # Auto axes: GSPMD propagates the plan's shardings (jax.make_mesh
        # defaults to Explicit axes, under which e.g. the embedding gather
        # of a vocab-sharded table is a type error)
        return jax.make_mesh(self.shape, self.axis_names,
                             devices=devs[:self.n_devices],
                             axis_types=(AxisType.Auto,) * len(self.shape))

    @classmethod
    def from_mesh(cls, mesh) -> "Topology":
        dims = dict(zip(mesh.axis_names, np.shape(mesh.devices)))
        return cls(dp=int(dims.get("data", 1)), tp=int(dims.get("model", 1)),
                   pods=int(dims.get("pod", 1)))

    @classmethod
    def production(cls, *, multi_pod: bool = False) -> "Topology":
        """The 16x16 (or 2x16x16) production shape of launch/mesh.py."""
        return cls(dp=16, tp=16, pods=2 if multi_pod else 1)

    # -- elastic -----------------------------------------------------------

    def shrink(self, n_devices: int) -> "Topology":
        """Topology after losing devices: keep tp if it still divides the
        survivor count (halving it otherwise, per elastic.plan_remesh) and
        fold pods into a single flat data axis.  dp never grows."""
        from repro.distributed import elastic
        (data, model), _ = elastic.plan_remesh(
            max(1, int(n_devices)), model=self.tp)
        return dataclasses.replace(
            self, pods=1, dp=min(data, self.dp * self.pods), tp=model)


# -------------------------------------------------------------------------
# cache specs (serving KV / recurrent state, batch-wise + head-wise)
# -------------------------------------------------------------------------

def _cache_leaf_spec(path, leaf, topo: Topology, batch: int) -> P:
    """Spec for one serving-cache leaf.  Keyed on the leaf name (the cache
    trees are flat dicts per layer): slot/batch dim shards over the dp
    axes when divisible, head-ish dims over "model" when divisible."""
    names = _rules._names(path)
    name = names[-1] if names else ""
    stacked = "stacked" in names
    shape = tuple(leaf.shape)
    core = shape[1:] if stacked else shape
    nd = len(core)
    tp = topo.tp
    dp = (topo.dp_axes if topo.dp_extent > 1 and nd >= 1
          and core[0] == batch and batch % topo.dp_extent == 0 else None)

    def out(parts) -> P:
        parts = list(parts)[:nd] + [None] * (nd - len(parts))
        return P(*(((None,) + tuple(parts)) if stacked else tuple(parts)))

    if name == "pos_pages":
        return out([None] * nd)
    if name in ("k_pages", "v_pages") and nd == 4:
        m = "model" if tp > 1 and core[2] % tp == 0 else None
        return out([None, None, m, None])
    if name in ("k", "v") and nd == 4:
        for i in (2, 3):
            if tp > 1 and core[i] % tp == 0:
                parts = [dp, None, None, None]
                parts[i] = "model"
                return out(parts)
        return out([dp, None, None, None])
    if name == "conv" and nd == 3:
        m = "model" if tp > 1 and core[2] % tp == 0 else None
        return out([dp, None, m])
    if name in ("ssm", "wkv", "s") and nd == 4:
        parts = [dp, None, None, None]
        for i in (1, 2, 3):
            if tp > 1 and core[i] % tp == 0:
                parts[i] = "model"
                break
        return out(parts)
    # pos tables, shift buffers, ssd token buffers, page tables: batch-wise
    return out([dp] + [None] * (nd - 1))


# -------------------------------------------------------------------------
# ShardingPlan
# -------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardingPlan:
    """PartitionSpecs for one (topology, param tree[, cache tree]) triple,
    resolved once and reused for every jit placement."""

    topology: Topology
    params: Any                 # PartitionSpec pytree matching the params
    batch: P                    # (B, ...) activation spec
    caches: Any = None          # PartitionSpec pytree matching the caches

    # -- constructors ------------------------------------------------------

    @classmethod
    def for_tree(cls, tree: Any, topology: Topology | None = None,
                 *, validate: bool = True) -> "ShardingPlan":
        """Resolve specs against an existing param pytree (master or
        serving format — packed slabs inherit the master spec)."""
        topo = topology or Topology()
        specs = jax.tree_util.tree_map_with_path(_rules._leaf_spec, tree)
        plan = cls(topology=topo, params=specs, batch=topo.batch_spec())
        if validate:
            plan.validate(tree)
        return plan

    @classmethod
    def for_config(cls, cfg, topology: Topology | None = None,
                   *, serving: bool = True,
                   validate: bool = True) -> "ShardingPlan":
        """Resolve specs for a model config without materializing weights
        (``jax.eval_shape`` over init + export)."""
        from repro.models import model as MD

        def build():
            p = MD.init_params(jax.random.PRNGKey(0), cfg)
            return MD.export_serving(p, cfg) if serving else p
        tree = jax.eval_shape(build)
        return cls.for_tree(tree, topology, validate=validate)

    def with_caches(self, caches: Any, *, batch: int) -> "ShardingPlan":
        """Attach cache specs resolved against an actual cache pytree.
        ``batch`` is the slot count — the dp axes apply only to dims that
        equal it and divide by the dp extent."""
        topo = self.topology
        specs = jax.tree_util.tree_map_with_path(
            lambda pth, leaf: _cache_leaf_spec(pth, leaf, topo, batch),
            caches)
        return dataclasses.replace(self, caches=specs)

    # -- validation / inspection ------------------------------------------

    def _iter_spec_leaves(self, tree: Any):
        flat_s = jax.tree_util.tree_flatten_with_path(
            self.params, is_leaf=lambda x: isinstance(x, P))[0]
        flat_t = jax.tree_util.tree_flatten_with_path(tree)[0]
        if len(flat_s) != len(flat_t):
            raise ValueError(
                f"plan/tree structure mismatch: {len(flat_s)} specs vs "
                f"{len(flat_t)} leaves — re-resolve the plan for this tree")
        for (ps, spec), (pt, leaf) in zip(flat_s, flat_t):
            yield "/".join(_rules._names(pt)), spec, leaf

    def validate(self, tree: Any) -> "ShardingPlan":
        """Check every sharded dim divides its axis extent; raise with a
        per-leaf report otherwise.  Returns self for chaining."""
        bad = []
        for name, spec, leaf in self._iter_spec_leaves(tree):
            shape = tuple(getattr(leaf, "shape", ()))
            for i, axes in enumerate(tuple(spec)):
                if axes is None:
                    continue
                axes = (axes,) if isinstance(axes, str) else tuple(axes)
                ext = math.prod(self.topology.axis_size(a) for a in axes)
                if i >= len(shape) or shape[i] % ext != 0:
                    bad.append(f"  {name}: shape {shape} dim {i} not "
                               f"divisible by {'*'.join(axes)}={ext} "
                               f"(spec {spec})")
        if bad:
            raise ValueError(
                "ShardingPlan does not fit this tree on "
                f"Topology{self.topology.shape}:\n" + "\n".join(bad))
        return self

    def replicated_leaves(self, tree: Any, min_ndim: int = 2) -> list[str]:
        """Paths of >=min_ndim-D leaves whose spec is fully replicated —
        the fall-through set tests pin so rule gaps are loud."""
        out = []
        for name, spec, leaf in self._iter_spec_leaves(tree):
            if getattr(leaf, "ndim", 0) >= min_ndim \
                    and all(a is None for a in tuple(spec)):
                out.append(name)
        return out

    def describe(self, tree: Any = None) -> str:
        """Human-readable table of the resolved layout."""
        topo = self.topology
        lines = [f"Topology(pods={topo.pods}, dp={topo.dp}, tp={topo.tp}) "
                 f"axes={topo.axis_names} shape={topo.shape}",
                 f"batch spec: {self.batch}"]
        if tree is not None:
            for name, spec, leaf in self._iter_spec_leaves(tree):
                shape = tuple(getattr(leaf, "shape", ()))
                lines.append(f"  {name:48s} {str(shape):24s} {spec}")
        else:
            flat = jax.tree_util.tree_flatten_with_path(
                self.params, is_leaf=lambda x: isinstance(x, P))[0]
            for pth, spec in flat:
                lines.append(f"  {'/'.join(_rules._names(pth)):48s} {spec}")
        if self.caches is not None:
            lines.append("cache specs:")
            flat = jax.tree_util.tree_flatten_with_path(
                self.caches, is_leaf=lambda x: isinstance(x, P))[0]
            for pth, spec in flat:
                lines.append(f"  {'/'.join(_rules._names(pth)):48s} {spec}")
        return "\n".join(lines)

    # -- materialization ---------------------------------------------------

    def named(self, mesh) -> Any:
        """NamedSharding tree for the params (jit in_shardings)."""
        return jax.tree.map(lambda s: NamedSharding(mesh, s), self.params,
                            is_leaf=lambda x: isinstance(x, P))

    def cache_named(self, mesh) -> Any:
        if self.caches is None:
            raise ValueError("plan has no cache specs; call with_caches()")
        return jax.tree.map(lambda s: NamedSharding(mesh, s), self.caches,
                            is_leaf=lambda x: isinstance(x, P))

    def zero1(self, shapes: Any, *, data_axis: str = "data",
              base: Any = None) -> Any:
        """Optimizer-moment specs: params specs + ZeRO-1 data-axis shard,
        with the once-per-tree unsharded-bytes summary (see
        sharding._zero1_specs).  ``base`` overrides the starting spec tree
        (e.g. an already-ZeRO'd tree to stack a second axis onto)."""
        return _rules._zero1_specs(
            self.params if base is None else base, shapes,
            data_size=self.topology.axis_size(data_axis),
            data_axis=data_axis)
