"""Pipeline-parallel stage executor: toy-scale correctness in a subprocess
(needs >1 device for the 'pod' pipeline axis)."""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PROG = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
mesh = make_mesh((4,), ("pod",))
from repro.distributed.pipeline import pipeline_apply

n_stages, n_micro, mb, d = 4, 8, 2, 16
rng = np.random.default_rng(0)
ws = jnp.asarray(rng.normal(size=(n_stages, d, d)).astype(np.float32) * 0.3)
bs = jnp.asarray(rng.normal(size=(n_stages, d)).astype(np.float32) * 0.1)
x = jnp.asarray(rng.normal(size=(n_micro, mb, d)).astype(np.float32))

def stage_fn(p, xm):
    w, b = p
    return jnp.tanh(xm @ w + b)

with jax.set_mesh(mesh):
    y = jax.jit(lambda p, xx: pipeline_apply(stage_fn, p, xx, mesh))((ws, bs), x)

# sequential reference
ref = x
for sidx in range(n_stages):
    ref = jnp.tanh(ref @ ws[sidx] + bs[sidx])
err = float(jnp.abs(jnp.asarray(y) - ref).max())
assert err < 1e-5, err
print("PIPELINE_OK", err)
"""


def test_pipeline_matches_sequential():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", PROG], env=env,
                         capture_output=True, text=True, timeout=300)
    assert "PIPELINE_OK" in out.stdout, out.stdout + out.stderr
