"""Manual shard_map MoE paths (ep / cap / ffn) must match the single-device
einsum path exactly — run on an 8-device host-emulated (data=2, model=4)
mesh in a subprocess."""
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config, reduce_config
    from repro.models import moe as moe_lib
    from repro.models.api import init_params
    from repro.parallel.sharding import Sharder, make_sharder

    from jax.sharding import AxisType
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    base = dataclasses.replace(
        reduce_config(get_config("granite-moe-3b-a800m")),
        d_model=32, d_ff=64, num_experts=4, num_experts_per_token=2,
        moe_capacity_factor=8.0)   # no drops → paths must agree exactly

    params = init_params(jax.random.PRNGKey(0), moe_lib.moe_defs(base),
                         jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 64, base.d_model))

    ref, _ = moe_lib.moe_layer(params, x, base, Sharder())

    for impl in ("ep", "cap", "ffn", "gspmd"):
        cfg = dataclasses.replace(base, moe_impl=impl)
        sharder = make_sharder(cfg, mesh)
        with mesh:
            out, aux = jax.jit(
                lambda p, x: moe_lib.moe_layer(p, x, cfg, sharder))(params, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4), impl
        print("impl", impl, "OK")

    # gradients must agree too (the shard_map transposes)
    def loss(p, impl):
        cfg = dataclasses.replace(base, moe_impl=impl)
        sharder = make_sharder(cfg, mesh) if impl != "ref" else Sharder()
        out, aux = moe_lib.moe_layer(p, x, cfg, sharder)
        return jnp.sum(out ** 2) + aux["moe_aux_loss"]

    g_ref = jax.grad(lambda p: loss(p, "ref"))(params)
    for impl in ("ep", "cap", "ffn"):
        with mesh:
            g = jax.jit(jax.grad(lambda p: loss(p, impl)))(params)
        for kref, kg in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g)):
            np.testing.assert_allclose(np.asarray(kg), np.asarray(kref),
                                       rtol=2e-3, atol=2e-4)
        print("grad", impl, "OK")
    print("MOE_SHARDED_OK")
""")


@pytest.mark.slow
def test_moe_manual_modes_match_reference():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
    assert "MOE_SHARDED_OK" in res.stdout
