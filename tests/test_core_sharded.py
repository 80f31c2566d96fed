"""Distributed matching paths under a real (host-emulated) multi-device mesh.

These run in a subprocess because XLA pins the platform device count at first
init — the main test process must keep seeing 1 device.
"""
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.core import (Extents, make_uniform_workload, sbm_count_sharded,
                            rank_count_sharded, bf_count_sharded,
                            brute_force_count_numpy)
    from repro.core.prefix import shard_inclusive_cumsum
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    import numpy as np

    assert len(jax.devices()) == 8, jax.devices()
    mesh = jax.make_mesh((8,), ("p",))

    # distributed two-level scan == cumsum
    x = jax.random.randint(jax.random.PRNGKey(0), (64,), -5, 6)
    fn = shard_map(lambda s: shard_inclusive_cumsum(s, "p"), mesh=mesh,
                   in_specs=P("p"), out_specs=P("p"))
    np.testing.assert_array_equal(np.asarray(fn(x)), np.cumsum(np.asarray(x)))

    key = jax.random.PRNGKey(42)
    subs, upds = make_uniform_workload(key, 300, 340, alpha=10.0, length=1000.0)
    want = brute_force_count_numpy(subs, upds)
    got_sbm = int(sbm_count_sharded(subs, upds, mesh, "p"))
    got_rank = int(rank_count_sharded(subs, upds, mesh, "p"))
    # bf shard path needs n divisible by shards: 300 % 8 != 0 → pad inert subs
    pad = (-300) % 8
    subs_p = Extents(jnp.concatenate([subs.lo, jnp.full((pad,), jnp.inf)]),
                     jnp.concatenate([subs.hi, jnp.full((pad,), -jnp.inf)]))
    got_bf = int(bf_count_sharded(subs_p, upds, mesh, "p", block=64))
    assert got_sbm == want, (got_sbm, want)
    assert got_rank == want, (got_rank, want)
    assert got_bf == want, (got_bf, want)

    # distributed pair enumeration == brute-force pair set
    from repro.core import sbm_enumerate_sharded, brute_force_pairs_numpy
    want_pairs = brute_force_pairs_numpy(subs, upds)
    pairs, cnt = sbm_enumerate_sharded(subs, upds, mesh, "p",
                                       max_pairs=len(want_pairs) + 32)
    got_pairs = {(int(i), int(j)) for i, j in np.asarray(pairs) if i >= 0}
    assert int(cnt) == len(want_pairs), (int(cnt), len(want_pairs))
    assert got_pairs == want_pairs
    # the buffer stays row-sharded: max_pairs rounded up to 8 slot ranges
    assert pairs.shape == (-(-(len(want_pairs) + 32) // 8) * 8, 2)
    assert len(pairs.sharding.device_set) == 8
    assert not pairs.sharding.is_fully_replicated
    # a buffer below K drops pairs, never the count: it holds the first
    # max_pairs of them
    capped, cnt_c = sbm_enumerate_sharded(subs, upds, mesh, "p",
                                          max_pairs=len(want_pairs) // 2)
    got_c = {(int(i), int(j)) for i, j in np.asarray(capped) if i >= 0}
    assert int(cnt_c) == len(want_pairs) and got_c < want_pairs
    assert len(got_c) == len(want_pairs) // 2

    # d-dim bit-matrix sharded over subscription rows (n not a shard
    # multiple -> inert-row padding): words and count must equal the
    # single-device packed matrix and the brute-force K
    from repro.core import bitmatrix_sharded, bitmatrix_words, make_tall_thin_workload
    subs2, upds2 = make_tall_thin_workload(jax.random.PRNGKey(7), 101, 90,
                                           alpha=8.0, d=2, length=1000.0)
    words, cnt2 = bitmatrix_sharded(subs2, upds2, mesh, "p")
    assert words.shape[0] == 104 and not words.sharding.is_fully_replicated
    np.testing.assert_array_equal(np.asarray(words)[:101],
                                  np.asarray(bitmatrix_words(subs2, upds2)))
    assert not np.asarray(words)[101:].any()     # inert padding rows
    from repro.core import brute_force_pairs_numpy as bf_pairs
    assert int(cnt2) == len(bf_pairs(subs2, upds2)), int(cnt2)

    # K >= 2^31 across shards (duplicated extents): without x64 the count
    # must pin at the sentinel and the buffer hold genuine pairs, each once
    n = m = 1 << 16
    big_s = Extents(jnp.zeros(n, jnp.float32), jnp.ones(n, jnp.float32))
    big_u = Extents(jnp.full(m, 0.5, jnp.float32), jnp.full(m, 2.0, jnp.float32))
    pairs_o, cnt_o = sbm_enumerate_sharded(big_s, big_u, mesh, "p",
                                           max_pairs=16)
    big_k = int(sbm_count_sharded(big_s, big_u, mesh, "p"))
    if jax.config.read("jax_enable_x64"):
        assert int(cnt_o) == n * m
        assert big_k == n * m
    else:
        assert int(cnt_o) == 2**31 - 1, int(cnt_o)
        rows = np.asarray(pairs_o)
        assert np.all((rows >= 0) & (rows < n))
        assert len({(int(i), int(j)) for i, j in rows}) == 16
        assert big_k == 2**31 - 1, big_k    # saturates, never wraps
    print("SHARDED_OK", want)
""")


@pytest.mark.slow
def test_sharded_matching_8_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
    assert "SHARDED_OK" in res.stdout
