"""Partition-rule and mesh tests.

Rule-table tests run against fabricated meshes via Mesh(np.array(...))
abstract construction where possible; the full 512-device behaviour is
exercised in a subprocess (XLA device count is locked at first init, so
the main test process stays single-device).
"""
import subprocess
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_host_mesh
from repro.sharding import partition as PT


def test_host_mesh_rules_replicate():
    """On a (1,1) mesh every rule is divisibility-guarded to no-op."""
    mesh = make_host_mesh()
    params = {"blocks": {"attn": {"wq": jnp.zeros((4, 64, 32))},
                         "mlp": {"w_down": jnp.zeros((4, 32, 64))}}}
    specs = PT.make_param_specs(params, mesh)
    leaves = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P))
    assert all(isinstance(s, P) for s in leaves)


def test_constrain_noop_without_mesh():
    x = jnp.zeros((8, 4))
    y = PT.constrain(x, ("pod", "data"), "model")
    assert y.shape == x.shape


def test_constrain_divisibility_guard():
    mesh = make_host_mesh()
    with PT.active_mesh(mesh):
        # (7,) doesn't divide anything — must silently no-op, not raise
        y = PT.constrain(jnp.zeros((7, 3)), "data", "model")
        assert y.shape == (7, 3)


_SUBPROC_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.sharding import partition as PT

mesh = make_mesh((2, 8), ("data", "model"))

# --- dense rules ---
params = {
    "embed": jax.ShapeDtypeStruct((1024, 64), jnp.float32),
    "blocks": {
        "attn": {"wq": jax.ShapeDtypeStruct((4, 64, 64), jnp.float32),
                 "wo": jax.ShapeDtypeStruct((4, 64, 64), jnp.float32),
                 "q_norm": jax.ShapeDtypeStruct((4, 16), jnp.float32)},
        "mlp": {"w_gate": jax.ShapeDtypeStruct((4, 128, 64), jnp.float32)},
        "moe": {"experts": {"w_gate":
                jax.ShapeDtypeStruct((4, 16, 32, 64), jnp.float32)}},
    },
}
specs = PT.make_param_specs(params, mesh, PT.ShardingConfig(mode="train"))
assert specs["blocks"]["attn"]["wq"] == P(None, "model", "data"), specs
assert specs["blocks"]["attn"]["wo"] == P(None, "data", "model")
assert specs["blocks"]["attn"]["q_norm"] == P(None, None)
assert specs["blocks"]["mlp"]["w_gate"] == P(None, "model", "data")
assert specs["blocks"]["moe"]["experts"]["w_gate"] == P(None, "model", None, "data")
assert specs["embed"] == P("model", "data")

# --- compressed planes follow the dense out-dim ---
# (1024x512 weight -> 128 codec blocks, divisible by the 8-way model axis)
from repro.core.compressed import planned_packed_specs
pl = planned_packed_specs((1024, 512), stacked=(4,))
params_c = {"blocks": {"mlp": {"w_gate": pl}}}
specs_c = PT.make_param_specs(params_c, mesh,
                              PT.ShardingConfig(mode="serve",
                                                fsdp_weights=False))
assert specs_c["blocks"]["mlp"]["w_gate"].codes == P(None, "model", None), \
    specs_c["blocks"]["mlp"]["w_gate"].codes
# fsdp stacks data onto the plane block axis
specs_f = PT.make_param_specs(params_c, mesh,
                              PT.ShardingConfig(mode="serve",
                                                fsdp_weights=True))
assert specs_f["blocks"]["mlp"]["w_gate"].codes == P(None, ("data", "model"), None)

# --- caches: heads shard when divisible, else time ---
caches = {"blocks": {"k": jax.ShapeDtypeStruct((4, 8, 64, 8, 16), jnp.float32),
                     "v": jax.ShapeDtypeStruct((4, 8, 64, 4, 16), jnp.float32)}}
cs = PT.make_cache_specs(caches, mesh)
assert cs["blocks"]["k"] == P(None, ("data",), None, "model", None), cs
assert cs["blocks"]["v"] == P(None, ("data",), "model", None, None), cs

# --- data specs ---
ds = PT.make_data_specs({"tokens": jax.ShapeDtypeStruct((8, 16), jnp.int32)},
                        mesh)
assert ds["tokens"] == P(("data",), None)

# --- constrain inside jit with the active mesh ---
with mesh, PT.active_mesh(mesh):
    def f(x):
        return PT.constrain(x, "data", "model") * 2
    y = jax.jit(f)(jnp.zeros((4, 16)))
    ns = y.sharding
    assert ns.spec == P("data", "model"), ns

print("SUBPROC_OK")
"""


@pytest.mark.slow
def test_partition_rules_16dev_subprocess():
    r = subprocess.run([sys.executable, "-c", _SUBPROC_SCRIPT],
                       capture_output=True, text=True, timeout=600,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"})
    assert "SUBPROC_OK" in r.stdout, r.stdout + r.stderr


def test_train_state_specs_structure():
    from repro.train.optimizer import AdamWConfig, adamw_init, QMoment
    mesh = make_host_mesh()
    params = {"w": jnp.zeros((8, 512))}
    state = {"params": params,
             "opt": adamw_init(params, AdamWConfig(quantized_state=True,
                                                   qblock=128))}
    specs = PT.make_train_state_specs(state, mesh)
    qm = specs["opt"]["mu"]["w"]["m"]
    assert isinstance(qm, QMoment)
    assert isinstance(qm.q, P) and isinstance(qm.scale, P)


def test_shard_aligned_mesh_constants():
    from repro.launch.mesh import AXIS_DATA, AXIS_MODEL, AXIS_POD
    assert (AXIS_POD, AXIS_DATA, AXIS_MODEL) == ("pod", "data", "model")


_MOE_LOCAL_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import numpy as np, jax, jax.numpy as jnp
from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models import layers as L
from repro.sharding import partition as PT

cfg = dataclasses.replace(get_config("deepseek-v2-lite-16b").smoke,
                          capacity_factor=64.0)   # dropless => exact match
p = L.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, cfg.d_model)) * 0.5
y_g, aux_g = L.apply_moe(p, x, cfg)

mesh = make_mesh((2, 4), ("data", "model"))
cfg_l = dataclasses.replace(cfg, moe_local_dispatch=True)
with mesh, PT.active_mesh(mesh):
    y_l, aux_l = jax.jit(lambda p_, x_: L.apply_moe(p_, x_, cfg_l))(p, x)
assert float(jnp.abs(y_g - y_l).max()) < 1e-5, "local dispatch != global"
print("MOE_LOCAL_OK")
"""


@pytest.mark.slow
def test_moe_local_dispatch_matches_global_subprocess():
    """shard_map local-routing MoE (§Perf DP3) ≡ global dispatch when
    dropless (capacity semantics are per-shard otherwise)."""
    r = subprocess.run([sys.executable, "-c", _MOE_LOCAL_SCRIPT],
                       capture_output=True, text=True, timeout=600,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"})
    assert "MOE_LOCAL_OK" in r.stdout, r.stdout[-1500:] + r.stderr[-1500:]
