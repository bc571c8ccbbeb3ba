"""Multi-device semantics (8 virtual CPU devices via subprocess):
  * sharded train step == single-device step (pjit correctness)
  * MoE shard_map EP == local dispatch
  * pipeline-parallel forward == plain forward
  * compressed cross-pod psum ~= exact psum (int8 tolerance)
Run in a subprocess so the forced device count can't leak into other tests.
"""
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
# the parent test injects --xla_force_host_platform_device_count=8; keep a
# belt-and-braces append here for anyone running the script standalone
if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
AUTO = AxisType.Auto

from repro.configs import get_config, reduced
from repro.models import model as MD
from repro.models import moe as MOE
from repro.models.transformer import Runtime
from repro.optim import adamw

if jax.device_count() != 8:
    # non-CPU backends ignore the host-platform flag; nothing to test here
    print("DEVICE-COUNT-SKIP", jax.device_count(), jax.default_backend())
    raise SystemExit(0)

# ---- 1. sharded train step == single device ------------------------------
cfg = reduced(get_config("bitnet-1.3b"))
cfg = dataclasses.replace(cfg, ternary=dataclasses.replace(cfg.ternary, das=None))
mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=(AUTO,) * 2)
p = MD.init_params(jax.random.PRNGKey(0), cfg)
toks = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab)
batch = {"inputs": toks, "labels": jnp.roll(toks, -1, 1)}
rt1 = Runtime()
def lf(pp, bb):
    return MD.loss_fn(pp, cfg, bb, rt1)[0]
l_single = jax.jit(lf)(p, batch)
from repro.distributed.plan import ShardingPlan, Topology
pspec = ShardingPlan.for_tree(p, Topology.from_mesh(mesh),
                              validate=False).params
ns = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                            is_leaf=lambda x: isinstance(x, P))
bspec = {"inputs": NamedSharding(mesh, P("data")), "labels": NamedSharding(mesh, P("data"))}
with mesh:
    l_shard = jax.jit(lf, in_shardings=(ns(pspec), bspec))(p, batch)
np.testing.assert_allclose(float(l_single), float(l_shard), rtol=2e-5)
print("OK sharded-loss")

# gradients too
g1 = jax.jit(jax.grad(lf))(p, batch)
with mesh:
    g2 = jax.jit(jax.grad(lf), in_shardings=(ns(pspec), bspec))(p, batch)
err = max(float(jnp.abs(a - b).max()) for a, b in
          zip(jax.tree.leaves(g1), jax.tree.leaves(g2)))
assert err < 2e-4, err
print("OK sharded-grads", err)

# ---- 2. MoE shard_map EP == local ----------------------------------------
cfgm = reduced(get_config("qwen3-moe-30b-a3b"))
pm = MOE.moe_init(jax.random.PRNGKey(0), cfgm)
x = jax.random.normal(jax.random.PRNGKey(2), (8, 16, cfgm.d_model)) * 0.5
y_local = MOE.moe_apply(pm, cfgm, x)
y_ep = jax.jit(lambda pp, xx: MOE.moe_apply(
    pp, cfgm, xx, mesh=mesh, dp_axes=("data",), ep_axis="model"))(pm, x)
np.testing.assert_allclose(np.asarray(y_local), np.asarray(y_ep),
                           rtol=5e-4, atol=5e-4)
print("OK moe-ep")

# ---- 3. pipeline parallel == plain ---------------------------------------
from repro.distributed.pipeline import pipeline_apply
mesh_pp = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                        axis_types=(AUTO,) * 3)
W = jax.random.normal(jax.random.PRNGKey(3), (2, 16, 16)) * 0.3  # 2 stages
def stage_fn(w, xb):
    return jnp.tanh(xb @ w)
xb = jax.random.normal(jax.random.PRNGKey(4), (8, 16))
y_ref = stage_fn(W[1], stage_fn(W[0], xb))
y_pp = pipeline_apply(stage_fn, W, xb, mesh=mesh_pp, axis="pod",
                      n_microbatches=4)
np.testing.assert_allclose(np.asarray(y_pp), np.asarray(y_ref),
                           rtol=1e-5, atol=1e-5)
print("OK pipeline")

# PP backward
def loss_pp(w):
    return jnp.sum(pipeline_apply(stage_fn, w, xb, mesh=mesh_pp, axis="pod",
                                  n_microbatches=4) ** 2)
def loss_ref(w):
    return jnp.sum(stage_fn(w[1], stage_fn(w[0], xb)) ** 2)
gpp = jax.grad(loss_pp)(W)
gref = jax.grad(loss_ref)(W)
np.testing.assert_allclose(np.asarray(gpp), np.asarray(gref), rtol=1e-4,
                           atol=1e-4)
print("OK pipeline-grad")

# ---- 4. compressed cross-pod grad exchange --------------------------------
from repro.optim.grad import compressed_crosspod_mean, zeros_error
g = {"w": jax.random.normal(jax.random.PRNGKey(5), (64, 64))}
err0 = zeros_error(g)
mean, err1 = compressed_crosspod_mean(g, err0, mesh_pp, pod_axis="pod")
# Topology is accepted in place of a mesh (built internally)
mean_t, _ = compressed_crosspod_mean(g, err0, Topology(dp=2, tp=2, pods=2),
                                     pod_axis="pod")
np.testing.assert_allclose(np.asarray(mean_t["w"]), np.asarray(mean["w"]),
                           rtol=1e-6, atol=1e-6)
# identical grads on both pods -> mean == dequantized value, small error
rel = float(jnp.abs(mean["w"] - g["w"]).max() / jnp.abs(g["w"]).max())
assert rel < 0.02, rel
assert float(jnp.abs(err1["w"]).max()) > 0  # error feedback captured residual
print("OK compressed-psum", rel)
print("ALL-MULTIDEVICE-OK")
"""


@pytest.mark.slow
def test_multidevice_semantics():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    # virtual CPU devices only: the child must never reach for an
    # accelerator, which the parent process may already hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, env=env, cwd=os.path.dirname(
                           os.path.dirname(os.path.abspath(__file__))),
                       timeout=900)
    if "DEVICE-COUNT-SKIP" in r.stdout:
        pytest.skip("runner cannot provide 8 virtual CPU devices: "
                    + r.stdout.strip().splitlines()[-1])
    assert "ALL-MULTIDEVICE-OK" in r.stdout, r.stdout + "\n" + r.stderr
