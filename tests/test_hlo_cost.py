"""Loop-aware HLO cost model: validated against hand-computable workloads
(XLA:CPU's own cost_analysis counts while bodies once — the reason this
module exists; see launch/hlo_cost.py)."""
import os
import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from repro.launch.hlo_cost import HloCostModel
from jax.sharding import PartitionSpec as P, NamedSharding

# 1) scan of 10 dots == exactly 10 dots of flops
a = jax.ShapeDtypeStruct((512, 512), jnp.bfloat16)
def g(x):
    def body(c, _):
        return (c @ c) * 0.999, None
    y, _ = jax.lax.scan(body, x, None, length=10)
    return y
c = HloCostModel(jax.jit(g).lower(a).compile().as_text()).cost()
want = 10 * 2 * 512 ** 3
assert abs(c.flops - want) / want < 0.01, (c.flops, want)

# 2) grad of scan of 10 dots == 30 dots (1 fwd + 2 bwd per layer)
def g2(x):
    def loss(x):
        def body(c, _):
            return jnp.tanh(c @ c), None
        y, _ = jax.lax.scan(body, x, None, length=10)
        return jnp.sum(y.astype(jnp.float32) ** 2)
    return jax.value_and_grad(loss)(x)
c2 = HloCostModel(jax.jit(g2).lower(a).compile().as_text()).cost()
assert abs(c2.flops - 3 * want) / (3 * want) < 0.01, c2.flops

# 3) sharded matmul: per-device flops + all-reduce detected with ring cost
mesh = jax.make_mesh((4, 2), ("data", "model"), devices=jax.devices(),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
w1 = jax.ShapeDtypeStruct((256, 512), jnp.float32)
w2 = jax.ShapeDtypeStruct((512, 256), jnp.float32)
x = jax.ShapeDtypeStruct((64, 256), jnp.float32)
sh = lambda s: NamedSharding(mesh, s)
f = jax.jit(lambda x, a, b: (x @ a) @ b,
            in_shardings=(sh(P("data", None)), sh(P(None, "model")),
                          sh(P("model", None))))
c3 = HloCostModel(f.lower(x, w1, w2).compile().as_text()).cost()
exp = 2 * (2 * 64 * 256 * 512) / 8
assert abs(c3.flops - exp) / exp < 0.01, (c3.flops, exp)
assert c3.coll_counts.get("all-reduce", 0) >= 1
# all-reduce payload: per-device [16, 256] f32 over model=2 ring
s_bytes = 16 * 256 * 4
want_link = 2.0 * s_bytes * (2 - 1) / 2
assert abs(c3.link_bytes - want_link) / want_link < 0.01, c3.link_bytes
print("HLO-COST-OK")
"""


def test_hlo_cost_model_validates():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["JAX_PLATFORMS"] = "cpu"  # never reach for a chip the parent holds
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "HLO-COST-OK" in out.stdout
