"""Topology/ShardingPlan API (distributed/plan.py): zoo-wide spec
coverage with the replicated fall-through set pinned per arch, topology
algebra (shrink/dp_axes/mesh errors), validation failures, and the
legacy-shim deprecation contract.
"""
import re
import warnings

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_MODULES, get_config, reduced
from repro.distributed import sharding
from repro.distributed.plan import ShardingPlan, Topology
from repro.models import model as MD

ARCHS = sorted(ARCH_MODULES)

# Intentionally replicated >=2D serving leaves under tp=2, per arch
# (pattern-collapsed: [i] matches any layer index).  Anything new showing
# up here means a param-spec rule gap — extend sharding.py's rule sets,
# don't just re-pin.
REPLICATED_2D = {
    "gla-1.3b": {"layers/tail/[i]/gla/wa1"},
    "kimi-k2-1t-a32b": {"layers/tail/[i]/moe/router"},
    "mellum2-12b-a2.5b": {"layers/tail/[i]/moe/router"},
    "qwen3-moe-30b-a3b": {"layers/tail/[i]/moe/router"},
    "rwkv6-3b": {
        "layers/tail/[i]/rwkv/cr/packed", "layers/tail/[i]/rwkv/mix_c",
        "layers/tail/[i]/rwkv/mix_t", "layers/tail/[i]/rwkv/u",
        "layers/tail/[i]/rwkv/w_decay1", "layers/tail/[i]/rwkv/wr/packed",
    },
    "zamba2-2.7b": {
        "layers/tail/[i]/mamba/conv", "layers/tail/[i]/mamba/wb",
        "layers/tail/[i]/mamba/wc", "layers/tail/[i]/mamba/wdt",
    },
}


def _serving_tree(cfg):
    return jax.eval_shape(lambda: MD.export_serving(
        MD.init_params(jax.random.PRNGKey(0), cfg), cfg))


@pytest.mark.parametrize("arch", ARCHS)
def test_plan_covers_zoo_serving_tree(arch):
    cfg = reduced(get_config(arch))
    plan = ShardingPlan.for_config(cfg, Topology(tp=2), validate=False)
    tree = _serving_tree(cfg)
    # every leaf resolved: structure match is what _iter_spec_leaves checks
    n = sum(1 for _ in plan._iter_spec_leaves(tree))
    assert n == len(jax.tree.leaves(tree)) > 0
    rep = {re.sub(r"\[\d+\]", "[i]", p) for p in plan.replicated_leaves(tree)}
    assert rep == REPLICATED_2D.get(arch, set()), rep
    # describe() renders one row per leaf without error
    assert len(plan.describe(tree).splitlines()) >= n


@pytest.mark.parametrize("arch", ["bitnet-1.3b", "qwen3-moe-30b-a3b",
                                  "rwkv6-3b", "zamba2-2.7b"])
def test_plan_caches_cover_slot_state(arch):
    from repro.models.transformer import Runtime
    import jax.numpy as jnp
    cfg = reduced(get_config(arch))
    topo = Topology(dp=2, tp=2)
    caches = jax.eval_shape(lambda: MD.init_caches(
        None, cfg, 4, 64, Runtime(), jnp.float32))
    plan = ShardingPlan.for_config(cfg, topo, validate=False)
    plan = plan.with_caches(caches, batch=4)
    specs = jax.tree.leaves(plan.caches, is_leaf=lambda x: isinstance(x, P))
    assert len(specs) == len(jax.tree.leaves(caches))
    # the slot/batch dim rides the dp axes somewhere in the tree
    assert any("data" in str(s) for s in specs)


def test_topology_algebra():
    t = Topology(dp=2, tp=2)
    assert t.axis_names == ("data", "model") and t.shape == (2, 2)
    assert t.n_devices == 4 and t.dp_extent == 2
    assert t.dp_axes_for(4) == ("data",) and t.dp_axes_for(3) == ()
    tp2 = Topology(dp=16, tp=16, pods=2)
    assert tp2.axis_names == ("pod", "data", "model")
    assert tp2.batch_spec() == P(("pod", "data"))
    assert tp2.batch_spec(sequence_sharded=True) == P(None, ("pod", "data"))
    assert Topology.production(multi_pod=True) == tp2
    with pytest.raises(ValueError):
        Topology(dp=0)


def test_topology_shrink_prefers_tp():
    # tp survives whole when it divides the survivor count; dp never grows
    assert Topology(dp=2, tp=2).shrink(2) == Topology(dp=1, tp=2)
    assert Topology(dp=4, tp=2).shrink(7) == Topology(dp=4, tp=1)
    assert Topology(dp=2, tp=2, pods=2).shrink(4) == Topology(dp=2, tp=2)
    assert Topology(dp=1, tp=1).shrink(0) == Topology(dp=1, tp=1)


def test_build_mesh_actionable_error():
    need = len(jax.devices()) + 1
    with pytest.raises(RuntimeError,
                       match=f"host_platform_device_count={need}"):
        Topology(dp=need).build_mesh()
    # and from_mesh round-trips a buildable topology
    t = Topology()
    assert Topology.from_mesh(t.build_mesh()) == t


def test_validate_reports_indivisible_leaves():
    cfg = reduced(get_config("bitnet-1.3b"))
    with pytest.raises(ValueError, match="not.*divisible|divisible"):
        ShardingPlan.for_config(cfg, Topology(tp=7))
    # and the permissive path still resolves specs
    plan = ShardingPlan.for_config(cfg, Topology(tp=7), validate=False)
    assert plan.params is not None


def test_legacy_shims_warn_once():
    cfg = reduced(get_config("bitnet-1.3b"))
    tree = jax.eval_shape(lambda: MD.init_params(jax.random.PRNGKey(0), cfg))
    sharding._DEPRECATION_WARNED.clear()
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        s1 = sharding.param_specs(tree)
        s2 = sharding.param_specs(tree)   # second call: no second warning
    dep = [w for w in rec if issubclass(w.category, DeprecationWarning)]
    assert len(dep) == 1 and "ShardingPlan" in str(dep[0].message)
    # shim output matches the plan API bit-for-bit
    assert s1 == s2 == ShardingPlan.for_tree(tree, validate=False).params
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        assert sharding.batch_spec(True) == \
            Topology(pods=2, dp=1).batch_spec()
    assert any(issubclass(w.category, DeprecationWarning) for w in rec)


def test_zero1_unsharded_summary_warning():
    cfg = reduced(get_config("bitnet-1.3b"))
    tree = jax.eval_shape(lambda: MD.init_params(jax.random.PRNGKey(0), cfg))
    plan = ShardingPlan.for_tree(tree, Topology(dp=7), validate=False)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        z = plan.zero1(tree)
    msgs = [str(w.message) for w in rec if "stay unsharded" in str(w.message)]
    assert len(msgs) == 1 and "data=7" in msgs[0]
    # nothing divides by 7 in the reduced config -> all moments unsharded
    assert all("data" not in str(s) for s in
               jax.tree.leaves(z, is_leaf=lambda x: isinstance(x, P)))
