"""The planned sweep on a mesh of four (host-emulated) devices.

The endpoint stream is sorted across the mesh (each chip sorts its own
records, the chips agree on exact splitters, one all_to_all hands each
its share, each sorts again) and the emission writes each chip's own
slots.  Every case runs the same checks: the sorted stream is the one
``encode_endpoints`` makes, the planned call on the mesh returns the
reference's pair set and exact K and the one-chip call's pair set, and
the stream and the pair buffer stay sharded.  The cases run in one
subprocess, because XLA pins the device count at its first use.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import json, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import (Extents, sbm_enumerate_planned,
                            sbm_enumerate_sharded)
    from repro.core.enumerate import _slot_map
    from repro.core.runtime import pairs_via_retry
    from repro.core.sweep import (_decode_tags, encode_endpoints,
                                  sequential_sbm_pairs_numpy,
                                  _sort_count_sharded)

    assert len(jax.devices()) == 4, jax.devices()
    mesh = jax.make_mesh((4,), ("p",))
    TOP = 2**31 - 1

    def ext(lo, hi, dtype):
        return Extents(jnp.asarray(lo, dtype), jnp.asarray(hi, dtype))

    def rand(seed, n, m, dtype, span, longest):
        rng = np.random.default_rng(seed)
        if dtype == "int32":
            lo = rng.integers(0, span, n + m)
            hi = np.minimum(lo + rng.integers(0, longest + 1, n + m), TOP)
        else:
            lo = rng.uniform(-span, span, n + m).astype(np.float32)
            lo[::7] = -0.0
            lo[1::7] = 0.0
            hi = lo + rng.uniform(0, longest, n + m).astype(np.float32)
        return ext(lo[:n], hi[:n], dtype), ext(lo[n:], hi[n:], dtype)

    def ordered(n, m, dtype, reverse):
        # each device's slice of the bounds lies in one key range, so its
        # records all go to one shard (the same one, or the mirror)
        lo = np.arange(n + m) * 4
        lo = lo[::-1] if reverse else lo
        return ext(lo[:n], lo[:n] + 6, dtype), ext(lo[n:], lo[n:] + 6, dtype)

    CASES = {
        "random_i32": lambda: rand(1, 301, 237, "int32", 500, 20),
        "random_f32": lambda: rand(2, 280, 333, "float32", 100.0, 3.0),
        "wide_i32": lambda: rand(3, 128, 96, "int32", TOP - 64, 2**27),
        "ascending_i32": lambda: ordered(64, 64, "int32", False),
        "descending_f32": lambda: ordered(60, 68, "float32", True),
        "all_equal_i32": lambda: (ext([7] * 33, [7] * 33, "int32"),
                                  ext([7] * 21, [7] * 21, "int32")),
        "range_ends_i32": lambda: (
            ext([0, 0, TOP, TOP - 1, 5], [0, TOP, TOP, TOP, TOP - 2],
                "int32"),
            ext([0, TOP, TOP - 2, 1], [1, TOP, TOP - 1, 4], "int32")),
        "n1_f32": lambda: (ext([3.0], [9.0], "float32"),
                           rand(4, 1, 50, "float32", 10.0, 2.0)[1]),
        "m1_i32": lambda: (rand(5, 50, 1, "int32", 30, 5)[0],
                           ext([10], [12], "int32")),
        "k0_i32": lambda: (ext(np.arange(40), np.arange(40), "int32"),
                           ext(np.arange(100, 130), np.arange(100, 130),
                               "int32")),
    }

    def pair_set(pairs):
        a = np.asarray(pairs)
        return {(int(i), int(j)) for i, j in a if i >= 0}

    out = {}
    for name, make in CASES.items():
        subs, upds = make()
        n, m = subs.size, upds.size
        want = sequential_sbm_pairs_numpy(subs, upds)
        tags, _ = _sort_count_sharded(subs, upds, mesh=mesh, axis_name="p")
        ep = encode_endpoints(subs, upds)
        is_sub, is_upper, owner = (np.asarray(x) for x in
                                   _decode_tags(tags, n, m))
        real = owner >= 0
        # padding extents' records are inert wherever they sort
        order = (np.array_equal(owner[real], np.asarray(ep.owner))
                 and np.array_equal(is_upper[real], np.asarray(ep.is_upper))
                 and np.array_equal(is_sub[real], np.asarray(ep.is_sub))
                 and real.sum() == 2 * (n + m))
        pairs, count, stats = sbm_enumerate_planned(subs, upds, mesh=mesh)
        one, count1, _ = sbm_enumerate_planned(subs, upds)
        out[name] = {
            "order": bool(order),
            "k": int(count), "count": stats.count, "want": len(want),
            "pairs_ok": pair_set(pairs) == want,
            "one_chip_ok": pair_set(pairs) == pair_set(one) and
                           int(count1) == int(count),
            "rows": int(pairs.shape[0]), "capacity": stats.capacity,
            "retries": stats.retries, "chips": stats.chips,
            "exchange_bytes": stats.exchange_bytes,
            "sharded": [len(x.sharding.device_set) == 4 and
                        not x.sharding.is_fully_replicated
                        for x in (tags, pairs)],
        }

    # K above the first bucket: the emission on the mesh under the retry
    # loop grows the buffer and then holds every pair
    subs, upds = CASES["random_i32"]()
    got = pairs_via_retry(
        lambda s, u, max_pairs: sbm_enumerate_sharded(s, u, mesh, "p",
                                                      max_pairs=max_pairs),
        subs, upds, start_cap=8)
    out["retry"] = {"ok": got == sequential_sbm_pairs_numpy(subs, upds)}

    # K = 2^32 with every pair emitted inside one shard: identical
    # extents put the 2^16 subscription uppers, each emitting 2^16 pairs,
    # in one shard's range.  That shard's offsets run past 2^31 while the
    # others emit nothing; on either slot map the count pins at the
    # sentinel and every row is a genuine pair, each once.
    same = ext(np.full(2**16, 9), np.full(2**16, 12), "int32")
    for form, cap in (("search", 16), ("expand", 2**15)):
        pairs, count = sbm_enumerate_sharded(same, same, mesh, "p",
                                             max_pairs=cap)
        a = np.asarray(pairs)
        out["saturated_" + form] = {
            "count": int(count), "rows": int(a.shape[0]),
            "genuine": bool(((a >= 0) & (a < 2**16)).all()),
            "distinct": len({(int(i), int(j)) for i, j in a})}
    # Each emitter on the mesh reads its opening rank from the join table
    # that its extent's lower record wrote, on whatever shard that lies.
    # Every case runs both slot maps, at a buffer of K (search) and of
    # 2^13 rows (expand).
    def cat(*parts):
        return np.concatenate([np.asarray(p, np.int64) for p in parts])

    def crossing(dtype, swap):
        # three long extents open in shard 0 and close in shard 3; between
        # lie same-side points, which never match them, and 20 counterpart
        # points; 400 counterpart points lie outside
        lo_long = 1000 + np.arange(3)
        same = 2000 + 800 * np.arange(1000)
        other = cat(1 + 4 * np.arange(200), 5003 + 40000 * np.arange(20),
                    900001 + 4 * np.arange(200))
        a = ext(cat(lo_long, same), cat(899000 - np.arange(3), same), dtype)
        b = ext(other, other, dtype)
        return ((b, a) if swap else (a, b)), 3

    def upper_only():
        # 600 nested subscriptions open before the last shard and all close
        # in it; the updates are points below them, but four inside
        lo = 1000 + 2 * np.arange(600)
        upd = cat(2 * np.arange(396), [1003, 1011, 1021, 1041])
        return (ext(lo, lo + 1000000, "int32"), ext(upd, upd, "int32")), 0

    JOIN_CASES = {
        "cross_subs_i32": lambda: crossing("int32", False),
        "cross_upds_f32": lambda: crossing("float32", True),
        "upper_only_shard_i32": upper_only,
        "n_ne_m_i32": lambda: (rand(6, 60, 1500, "int32", 400000, 300), 0),
    }
    for name, make in JOIN_CASES.items():
        (subs, upds), n_long = make()
        n, m = subs.size, upds.size
        want = sequential_sbm_pairs_numpy(subs, upds)
        one, _, _ = sbm_enumerate_planned(subs, upds)
        tags, _ = _sort_count_sharded(subs, upds, mesh=mesh, axis_name="p")
        shard = tags.shape[0] // 4
        is_sub, is_upper, owner = (np.asarray(x) for x in
                                   _decode_tags(tags, n, m))
        real = owner >= 0
        where = np.arange(tags.shape[0]) // shard
        # the long extents are the first of the crossing side
        side = is_sub == (name != "cross_upds_f32")
        long_ = real & side & (owner < n_long)
        r = {"want": len(want), "n": n, "m": m,
             "lower_shards": sorted(set(where[long_ & ~is_upper].tolist())),
             "upper_shards": sorted(set(where[long_ & is_upper].tolist())),
             "upper_only_shards": [q for q in range(4) if real[where == q].any()
                                   and is_upper[real & (where == q)].all()]}
        for form, cap in (("search", max(len(want), 1)), ("expand", 2**13)):
            pairs, count = sbm_enumerate_sharded(subs, upds, mesh, "p",
                                                 max_pairs=cap)
            r[form] = {"form": _slot_map(cap, shard), "k": int(count),
                       "pairs_ok": pair_set(pairs) == want,
                       "one_chip_ok": pair_set(pairs) == pair_set(one)}
        out[name] = r
    print("MESH", json.dumps(out))
""")

CASES = ["random_i32", "random_f32", "wide_i32", "ascending_i32",
         "descending_f32", "all_equal_i32", "range_ends_i32", "n1_f32",
         "m1_i32", "k0_i32"]


@pytest.fixture(scope="module")
def results():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
    line, = [r for r in res.stdout.splitlines() if r.startswith("MESH ")]
    return json.loads(line[5:])


@pytest.mark.parametrize("case", CASES)
def test_stream_sorted_across_the_mesh_is_the_one_chip_stream(results, case):
    r = results[case]
    assert r["order"]


@pytest.mark.parametrize("case", CASES)
def test_planned_call_on_a_mesh_matches_the_reference(results, case):
    r = results[case]
    assert r["k"] == r["count"] == r["want"]
    assert r["pairs_ok"] and r["one_chip_ok"]
    assert r["retries"] == 0 and r["chips"] == 4
    assert r["rows"] >= r["capacity"] >= r["want"] and r["rows"] % 4 == 0
    assert r["exchange_bytes"] > 0


@pytest.mark.parametrize("case", CASES)
def test_stream_and_pair_buffer_stay_sharded(results, case):
    assert results[case]["sharded"] == [True, True]


def test_emission_on_a_mesh_retries_past_the_first_bucket(results):
    assert results["retry"]["ok"]


@pytest.mark.parametrize("form,rows", [("search", 16), ("expand", 2**15)])
def test_emission_on_a_mesh_blanks_when_one_shard_saturates(results, form,
                                                            rows):
    """K = 2³² inside one shard, without x64: the count pins at 2³¹−1 and
    the buffer is filled with genuine pairs, not blanked (the name is the
    contract this test once held)."""
    r = results["saturated_" + form]
    assert r["count"] == 2**31 - 1 and r["rows"] == rows
    assert r["genuine"] and r["distinct"] == rows


JOIN_CASES = ["cross_subs_i32", "cross_upds_f32", "upper_only_shard_i32",
              "n_ne_m_i32"]


@pytest.mark.parametrize("form", ["search", "expand"])
@pytest.mark.parametrize("case", JOIN_CASES)
def test_emitters_read_their_opening_rank_across_the_mesh(results, case,
                                                          form):
    """Each emitter's count and counterpart start come from its opening
    rank in the join table, written on the shard that holds its lower
    record: the pairs are the reference's and the one-chip call's."""
    r = results[case]
    assert r[form]["form"] == form
    assert r[form]["k"] == r["want"] > 0
    assert r[form]["pairs_ok"] and r[form]["one_chip_ok"]
    if case.startswith("cross_"):
        # the long extents open in shard 0 and close in shard 3
        assert r["lower_shards"] == [0] and r["upper_shards"] == [3]
    if case == "upper_only_shard_i32":
        assert 3 in r["upper_only_shards"]
    if case == "n_ne_m_i32":
        assert r["n"] != r["m"]


@pytest.mark.parametrize("x64", [False, True])
@pytest.mark.parametrize("form,max_pairs", [("expand", 1 << 13),
                                            ("search", 61)])
def test_emission_exchange_bytes_count_its_collectives(form, max_pairs, x64):
    """``MatchStats.exchange_bytes`` of the mesh emission at n ≠ m, term
    by term: a ring all-reduce moves 2(P−1)·X, an all-gather P(P−1)·X, an
    all-to-all or a reduce-scatter (P−1)·X, X bytes a chip."""
    import jax

    from repro.core.enumerate import _emit_exchange_bytes

    n, m, p = 1003, 420, 4
    c = 8 if x64 else 4                      # a count lane's bytes
    slots = p * -(-max_pairs // p)
    all_reduce, all_gather, one_way = 2 * (p - 1), p * (p - 1), p - 1
    want = (2 * all_gather * 4               # the lower cumsums' carries
            + 2 * all_reduce * 4 * (n + m)   # open_at and table psums
            + all_gather * 4                 # the shards' pinned totals
            + 4 * all_reduce * c)            # the count's lane partials
    if form == "expand":
        # per slot value (emitter code, base): the last record's value,
        # the marks' reduce-scatter, the slot scan's carry
        want += 2 * (all_gather * 4 + one_way * 4 * slots + all_gather * 4)
    else:
        want += one_way * 8 * slots          # the rows' all_to_all
    with jax.enable_x64(x64):
        assert _emit_exchange_bytes(n, m, p, max_pairs, form) == want
        assert _emit_exchange_bytes(n, m, 1, max_pairs, form) == 0
