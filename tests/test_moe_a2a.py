"""All-to-all (shard_map) MoE vs dense reference — runs on 8 fake devices.

XLA locks the device count at first jax init, so this test runs in a
subprocess with XLA_FLAGS set (the main pytest process keeps 1 device).
"""
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import MoESpec
from repro.models.moe import init_moe, moe_ffn_dense_reference
from repro.models.moe_a2a import moe_ffn_a2a

spec = MoESpec(n_experts=8, top_k=2, d_ff_expert=32, capacity_factor=8.0)
params = init_moe(jax.random.PRNGKey(0), 16, spec)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 16))
ref = moe_ffn_dense_reference(params, x, spec)

for shape, axes in [((2, 4), ("data", "model")), ((1, 8), ("data", "model"))]:
    mesh = jax.make_mesh(shape, axes)
    with jax.set_mesh(mesh):
        out = moe_ffn_a2a(params, x, spec, "swiglu", mesh, fsdp_axes=("data",))
    err = float(jnp.max(jnp.abs(np.asarray(out) - np.asarray(ref))))
    assert err < 2e-4, (shape, err)

# gradients match the dense reference
mesh = jax.make_mesh((2, 4), ("data", "model"))
def loss_a2a(p):
    return jnp.sum(moe_ffn_a2a(p, x, spec, "swiglu", mesh,
                               fsdp_axes=("data",)) ** 2)
with jax.set_mesh(mesh):
    g = jax.grad(loss_a2a)(params)
gref = jax.grad(lambda p: jnp.sum(moe_ffn_dense_reference(p, x, spec) ** 2))(params)
for k in g:
    e = float(jnp.max(jnp.abs(g[k] - gref[k])))
    assert e < 5e-4, (k, e)
print("A2A_MOE_OK")
"""


@pytest.mark.slow
def test_moe_a2a_subprocess():
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "A2A_MOE_OK" in out.stdout
