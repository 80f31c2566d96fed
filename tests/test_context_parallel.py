"""Context-parallel attention (halo window + ring) vs dense reference on an
emulated (data=2, model=4) mesh."""
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from repro.models.attention import dense_attention
    from repro.parallel.context_parallel import (halo_window_attention,
                                                 ring_attention, cp_specs)

    from jax.sharding import AxisType
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    b, h, kvh, s, hd = 2, 4, 2, 256, 16
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (b, h, s, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, kvh, s, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, kvh, s, hd))
    spec = cp_specs(mesh)

    # --- halo window ---
    for w in (16, 33, 64):
        fn = shard_map(
            lambda q, k, v, w=w: halo_window_attention(
                q, k, v, window=w, axis_name="model"),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False)
        got = fn(q, k, v)
        want = dense_attention(q, k, v, scale=hd ** -0.5, causal=True,
                               window=w, softcap=None)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        print("halo window", w, "OK")

    # --- ring (full causal) ---
    fn = shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="model"),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    got = fn(q, k, v)
    want = dense_attention(q, k, v, scale=hd ** -0.5, causal=True,
                           window=None, softcap=None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    print("ring OK")

    # --- ring with softcap (grok/gemma-style) ---
    fn = shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="model",
                                       softcap=20.0),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    got = fn(q, k, v)
    want = dense_attention(q, k, v, scale=hd ** -0.5, causal=True,
                           window=None, softcap=20.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    print("CP_OK")
""")


@pytest.mark.slow
def test_context_parallel_attention():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
    assert "CP_OK" in res.stdout
