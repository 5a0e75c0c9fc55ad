"""a2a MoE dispatch: exactness vs the dense dispatch and differentiability
(8 fake devices in a subprocess — the main test process keeps 1 device)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]

CODE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
from repro.configs.base import MoESpec
from repro.models.moe import init_moe, moe_ffn
from repro.models.moe_a2a import moe_ffn_a2a

from jax.sharding import AxisType
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(AxisType.Auto, AxisType.Auto))
spec = MoESpec(n_experts=8, top_k=2, d_expert=16, n_shared=1)
p = init_moe(jax.random.PRNGKey(0), 32, spec, jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32))
with mesh:
    ref, _ = moe_ffn(x, p, spec)
    out, _ = jax.jit(lambda x, p: moe_ffn_a2a(x, p, spec, mesh,
                                              slack=8.0))(x, p)
    def loss(p):
        o, _ = moe_ffn_a2a(x, p, spec, mesh, slack=8.0)
        return jnp.sum(o ** 2)
    g = jax.jit(jax.grad(loss))(p)
err = float(jnp.max(jnp.abs(out - ref)))
gnorm = float(jnp.linalg.norm(g["w_gate"]))
print(json.dumps({"err": err, "gnorm": gnorm}))
"""


@pytest.mark.slow
def test_moe_a2a_exact_and_differentiable():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    out = subprocess.run([sys.executable, "-c", CODE], env=env,
                         capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["err"] < 1e-5
    assert res["gnorm"] > 0
