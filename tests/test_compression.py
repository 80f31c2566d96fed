"""Int8 gradient compression: quantization bounds, error feedback
unbiasedness, and multi-device psum correctness (subprocess mesh)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.parallel.compression import (dequantize_int8, quantize_int8)

jax.config.update("jax_platform_name", "cpu")


def test_quantize_roundtrip_error_bound():
    x = jax.random.normal(jax.random.PRNGKey(0), (1000,)) * 3.0
    q, scale = quantize_int8(x)
    back = dequantize_int8(q, scale, 1000)
    # per-block max-abs scaling → error ≤ scale/2 per element
    blk_max = np.abs(np.asarray(x)).reshape(-1, 250 if False else 1)
    err = np.abs(np.asarray(back) - np.asarray(x))
    assert err.max() <= float(scale.max()) / 2 + 1e-6


def test_error_feedback_reduces_bias():
    """With error feedback the *running mean* of compressed grads converges
    to the true mean (unbiasedness over steps)."""
    from repro.parallel.compression import BLOCK
    rng = np.random.RandomState(0)
    g_true = jnp.asarray(rng.randn(512) * 0.01)
    err = jnp.zeros((512,))
    acc = np.zeros(512)
    steps = 60
    for _ in range(steps):
        target = g_true + err
        q, scale = quantize_int8(target)
        deq = dequantize_int8(q, scale, 512)
        err = target - deq
        acc += np.asarray(deq)
    drift = np.abs(acc / steps - np.asarray(g_true)).max()
    naive_once = np.abs(np.asarray(
        dequantize_int8(*quantize_int8(g_true), 512)) - np.asarray(g_true)).max()
    assert drift <= naive_once / 5   # feedback beats one-shot quantization


_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from repro.parallel.compression import compressed_psum

    from jax.sharding import AxisType
    mesh = jax.make_mesh((4,), ("pod",), axis_types=(AxisType.Auto,))
    g = jax.random.normal(jax.random.PRNGKey(0), (4, 1024)) * 0.01
    err = jnp.zeros((4, 1024))

    def body(g_l, e_l):
        out, err = compressed_psum(g_l[0], "pod", e_l[0])
        return out[None], err[None]

    fn = shard_map(body, mesh=mesh, in_specs=(P("pod"), P("pod")),
                   out_specs=(P("pod"), P("pod")), check_vma=False)
    out, new_err = fn(g, err)
    want = np.asarray(g).mean(axis=0)
    got = np.asarray(out)[0]
    # all shards agree and approximate the mean within int8 precision
    for i in range(4):
        np.testing.assert_allclose(np.asarray(out)[i], got, rtol=0, atol=0)
    scale_bound = np.abs(np.asarray(g)).max() / 127
    assert np.abs(got - want).max() <= scale_bound + 1e-7
    print("COMPRESSION_OK")
""")


@pytest.mark.slow
def test_compressed_psum_multidevice():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
    assert "COMPRESSION_OK" in res.stdout
