"""Sharding rules: parameter/activation PartitionSpecs for the production mesh.

Mesh axes: ("data", "model") single-pod, ("pod", "data", "model") multi-pod.
  * TP  — column-parallel projections shard their output dim on "model";
          row-parallel (wo / w_out) shard their input dim; the pair gives the
          Megatron pattern with one all-reduce per block half.
  * EP  — expert stacks shard experts on "model" (moe.py's shard_map psum).
  * DP  — batch shards on ("pod", "data"); ZeRO-1 shards optimizer moments
          further along "data" (zero1_specs).
  * Vocab — embedding/head shard the vocab dim on "model".

TWD-packed serving weights are packed along K (axis 0), so a packed leaf
inherits exactly the spec of its master weight: an N-dim ("model") shard
never splits a byte; a K-dim shard is padded by GSPMD (global decode is
written against logical K, so padding is inert).

Rules key on the nearest named ancestor in the param tree path; leaves under
the scan "stacked" stacks get a leading None for the group axis.
"""

from __future__ import annotations

import warnings
from typing import Any

import jax
from jax.sharding import PartitionSpec as P
from jax.tree_util import DictKey, SequenceKey

__all__ = ["param_specs", "zero1_specs", "batch_spec", "MODEL_AXIS"]


MODEL_AXIS = "model"

# nearest-ancestor name -> spec for the 2D master weight (in, out)
COL_PARALLEL = {"wq", "wk", "wv", "wg", "wz", "wx", "w_gate", "w_in", "ck",
                "shared_gate", "shared_in", "wa2", "w_decay2", "head"}
ROW_PARALLEL = {"wo", "w_out", "cv", "shared_out"}
EXPERT = {"experts_gate", "experts_in", "experts_out"}
VOCAB = {"embed"}
# 1-D leaves laid out along the model-sharded inner dim
INNER_VEC = {"w0", "ln_x"}
REPLICATED = {"router", "u", "wb", "wc", "wdt", "dt_bias", "a_log", "d_skip",
              "w_decay1", "wa1", "mix_t", "mix_c", "cr", "norm1", "norm2",
              "final_norm", "conv"}


def _names(path) -> list[str]:
    out = []
    for k in path:
        if isinstance(k, DictKey):
            out.append(str(k.key))
        elif isinstance(k, SequenceKey):
            out.append(f"[{k.idx}]")
    return out


def _leaf_spec(path, leaf) -> P:
    names = _names(path)
    ndim = getattr(leaf, "ndim", 0)
    stacked = "stacked" in names
    base: tuple

    def with_stack(spec: tuple) -> P:
        spec = tuple(spec[:ndim - (1 if stacked else 0)])
        return P(*(((None,) + spec) if stacked else spec))

    leaf_name = names[-1] if names else ""
    anc = [n for n in names if not n.startswith("[")]
    hit = None
    for n in reversed(anc):
        if n in COL_PARALLEL | ROW_PARALLEL | EXPERT | VOCAB | INNER_VEC \
                | REPLICATED or n == "mamba":
            hit = n
            break

    if leaf_name == "scale" and ndim <= 1 and hit not in INNER_VEC:
        return P()  # quantization / norm scalars and (d,) norm scales
    if hit in VOCAB:
        return with_stack((MODEL_AXIS, None))
    if hit in COL_PARALLEL:
        if ndim - (1 if stacked else 0) <= 1:
            return P()
        return with_stack((None, MODEL_AXIS))
    if hit in ROW_PARALLEL:
        if ndim - (1 if stacked else 0) <= 1:
            return P()
        return with_stack((MODEL_AXIS, None))
    if hit in EXPERT:
        return with_stack((MODEL_AXIS, None, None))
    if hit in INNER_VEC:
        return with_stack((MODEL_AXIS,) + (None,) * 3)
    if hit == "mamba" and leaf_name == "conv":
        return with_stack((None, MODEL_AXIS))
    if hit == "mamba" and leaf_name == "scale":  # mamba gated-norm over d_inner
        return with_stack((MODEL_AXIS,))
    return with_stack((None,) * 4)


def _param_specs(params: Any) -> Any:
    return jax.tree_util.tree_map_with_path(_leaf_spec, params)


def _dtype_bytes(shape) -> int:
    dt = getattr(shape, "dtype", None)
    itemsize = getattr(dt, "itemsize", 4) if dt is not None else 4
    size = 1
    for d in shape.shape:
        size *= d
    return size * itemsize


def _zero1_specs(specs: Any, shapes: Any, data_size: int = 16,
                 data_axis: str = "data") -> Any:
    """ZeRO-1 impl: insert `data_axis` into the first unsharded dimension
    whose size divides by the data-axis extent.  Leaves with no such dim
    stay on their param spec (explicit input shardings require exact
    divisibility) — but that is no longer silent: one summary warning per
    tree reports how many moment leaves / bytes stay unsharded."""
    skipped: list[tuple[int, int]] = [0, 0]  # leaves, bytes

    def one(spec: P, shape) -> P:
        parts = list(spec)
        parts += [None] * (len(shape.shape) - len(parts))
        for i, s in enumerate(parts):
            if s is None and shape.shape[i] % data_size == 0 \
                    and shape.shape[i] > 0:
                parts[i] = data_axis
                return P(*parts)
        skipped[0] += 1
        skipped[1] += _dtype_bytes(shape)
        return spec
    out = jax.tree.map(one, specs, shapes,
                       is_leaf=lambda x: isinstance(x, P))
    if skipped[0]:
        warnings.warn(
            f"zero1_specs: {skipped[0]} moment leaves "
            f"({skipped[1] / 2**20:.2f} MiB per moment) have no dim "
            f"divisible by {data_axis}={data_size} and stay unsharded "
            f"(replicated across the data axis)", stacklevel=3)
    return out


def _batch_spec(multi_pod: bool, *, sequence_sharded: bool = False) -> P:
    dp = ("pod", "data") if multi_pod else ("data",)
    if sequence_sharded:
        return P(None, dp)
    return P(dp)


# --------------------------------------------------------------------------
# deprecated entry points — new code goes through distributed/plan.py
# --------------------------------------------------------------------------

_DEPRECATION_WARNED: set = set()


def _warn_deprecated(old: str, new: str) -> None:
    if old not in _DEPRECATION_WARNED:   # once per process, not per trace
        _DEPRECATION_WARNED.add(old)
        warnings.warn(
            f"{old} is deprecated; use {new} (distributed/plan.py)",
            DeprecationWarning, stacklevel=3)


def param_specs(params: Any) -> Any:
    """Deprecated: use ``ShardingPlan.for_config(cfg)`` /
    ``ShardingPlan.for_tree(params)``."""
    _warn_deprecated("param_specs", "ShardingPlan.for_tree(params).params")
    return _param_specs(params)


def zero1_specs(specs: Any, shapes: Any, data_size: int = 16,
                data_axis: str = "data") -> Any:
    """Deprecated: use ``ShardingPlan.zero1(shapes)``."""
    _warn_deprecated("zero1_specs", "ShardingPlan.zero1(shapes)")
    return _zero1_specs(specs, shapes, data_size, data_axis)


def batch_spec(multi_pod: bool, *, sequence_sharded: bool = False) -> P:
    """Deprecated: use ``Topology.batch_spec()``."""
    _warn_deprecated("batch_spec", "Topology.batch_spec()")
    return _batch_spec(multi_pod, sequence_sharded=sequence_sharded)
