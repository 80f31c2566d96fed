"""The Pallas kernels compile for a TPU v5e at the sizes chip_smoke.py runs.

Nothing executes: each test lowers one kernel for a described (not
attached) v5e chip and compiles it, so a block shape the chip's tiling
refuses, an unsupported primitive or an SMEM/VMEM overrun fails here
rather than on the chip.  About two seconds each.  Sizes: the paper's
N = 10⁶ extents (2·10⁶ endpoints, n = 5·10⁵ bitmask ids per side) for the
sweep kernels, d = 2 and n = m = 8192 for the bit-matrix kernel.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core.errors import ValidationError
from repro.kernels import bitmatch, sbm_sweep

ENDPOINTS = 2_000_000
WORDS = -(-500_000 // 32)
# largest per-block pair total of the paper's uniform α = 100 set
# (N = 10⁶, 1024-endpoint blocks)
ALPHA100_CAP = 29_974
# the largest 1024-multiple cap pass C fits in SMEM at WORDS per side
MAX_CAP = max(c for c in range(1024, 1 << 17, 1024)
              if sbm_sweep.emit_pairs_smem_bytes(1024, WORDS, WORDS, c)
              <= sbm_sweep.SMEM_BUDGET)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _compiles_to_kernel(lowered) -> None:
    assert "tpu_custom_call" in lowered.compile().as_text()


def _stream(one_chip, block: int, rows=()):
    total = -(-ENDPOINTS // block) * block
    return jax.ShapeDtypeStruct(rows + (total,), jnp.int32,
                                sharding=one_chip)


def test_sweep_count_kernel_compiles(one_chip):
    deltas = _stream(one_chip, 2048, rows=(4,))
    _compiles_to_kernel(sbm_sweep.sweep_count_pallas.lower(
        deltas, block_size=2048, interpret=False))


def test_delta_bitmask_kernel_compiles(one_chip):
    ep = _stream(one_chip, 1024)
    _compiles_to_kernel(sbm_sweep.delta_bitmasks_pallas.lower(
        ep, ep, ep, num_words=WORDS, block_size=1024, interpret=False))


def test_pair_emission_kernel_compiles(one_chip):
    ep = _stream(one_chip, 1024)
    seeds = jax.ShapeDtypeStruct((ep.shape[0] // 1024, WORDS), jnp.uint32,
                                 sharding=one_chip)
    _compiles_to_kernel(sbm_sweep.sweep_emit_pairs_pallas.lower(
        ep, ep, ep, ep, seeds, seeds, block_size=1024, cap=1024,
        interpret=False))


@pytest.mark.parametrize("cap", [ALPHA100_CAP, MAX_CAP])
def test_pair_emission_kernel_compiles_at_large_caps(one_chip, cap):
    ep = _stream(one_chip, 1024)
    seeds = jax.ShapeDtypeStruct((ep.shape[0] // 1024, WORDS), jnp.uint32,
                                 sharding=one_chip)
    _compiles_to_kernel(sbm_sweep.sweep_emit_pairs_pallas.lower(
        ep, ep, ep, ep, seeds, seeds, block_size=1024, cap=cap,
        interpret=False))


def test_pair_emission_past_smem_budget_is_refused():
    ep = jax.ShapeDtypeStruct((1024 * 4,), jnp.int32)
    seeds = jax.ShapeDtypeStruct((4, WORDS), jnp.uint32)
    with pytest.raises(ValidationError, match="SMEM"):
        sbm_sweep.sweep_emit_pairs_pallas.lower(
            ep, ep, ep, ep, seeds, seeds, block_size=1024,
            cap=MAX_CAP + 1024, interpret=False)


def test_delta_bitmasks_past_smem_budget_is_refused():
    ep = jax.ShapeDtypeStruct((1024 * 4,), jnp.int32)
    with pytest.raises(ValidationError, match="SMEM"):
        sbm_sweep.delta_bitmasks_pallas.lower(
            ep, ep, ep, num_words=64 * 1024, block_size=1024,
            interpret=False)


def test_bitmatrix_kernel_compiles(one_chip):
    n = m = 8192
    d = 2

    def arr(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    subs = arr((n, d))
    upds = arr((32 * d, m // 32))
    _compiles_to_kernel(bitmatch._bitmatrix_pallas_jit.lower(
        subs, subs, upds, upds, block_n=256, word_block=m // 32,
        interpret=False))


COLLECTIVE = re.compile(r"\b(all-reduce|all-gather|all-to-all|reduce-scatter|"
                        r"collective-permute)(-start|-done)?\(")


MESH_FORMS = [("expand", 1 << 16), ("search", 64)]


def _mesh_programs(topo, form, max_pairs, n=4096, m=4096):
    """The planned sweep's probe and emission on a described v5e:2x2,
    lowered, with the shard's stream length."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core import Extents
    from repro.core.enumerate import _emit_sharded, _slot_map
    from repro.core.sweep import _sort_count_sharded

    mesh = Mesh(topo.devices, ("p",))
    where = NamedSharding(mesh, P("p"))

    def ext(size):
        return Extents(*(jax.ShapeDtypeStruct((size,), jnp.int32,
                                              sharding=where)
                         for _ in range(2)))

    probe = jax.jit(lambda s, u: _sort_count_sharded(s, u, mesh=mesh,
                                                     axis_name="p"))
    tags = jax.eval_shape(probe, ext(n), ext(m))[0]
    tags = jax.ShapeDtypeStruct(tags.shape, tags.dtype, sharding=where)
    shard = tags.shape[0] // 4
    assert _slot_map(max_pairs, shard) == form
    return (probe.lower(ext(n), ext(m)),
            _emit_sharded.lower(tags, n=n, m=m, max_pairs=max_pairs,
                                mesh=mesh, axis_name="p"), shard)


@pytest.mark.parametrize("form,max_pairs", MESH_FORMS)
def test_mesh_collectives_compile_as_synchronous_ops(topo, form, max_pairs):
    """Every collective of the planned sweep's two mesh programs compiles
    for a v5e:2x2 into one synchronous all-reduce or all-to-all
    operation: no reduce-scatter (the slot marks' psum_scatter is an
    all-reduce), no asynchronous start/done halves, none inside a fusion.
    A trace's operations of those kinds then cover the collectives'
    device time, which is what ``exchange_ms`` reads."""
    probe, emit, _ = _mesh_programs(topo, form, max_pairs)
    for lowered in (probe, emit):
        text = lowered.compile().as_text()
        kinds, fused = set(), set()
        computation = ""
        for line in text.splitlines():
            if not line.startswith(" ") and "{" in line:
                computation = line
            found = COLLECTIVE.search(line.partition(", metadata")[0])
            if found:
                kinds.add(found.group(1) + (found.group(2) or ""))
                if "fused" in computation:
                    fused.add(found.group(0))
        assert kinds and kinds <= {"all-reduce", "all-to-all"}, kinds
        assert not fused, fused


RANKS_OP = re.compile(r"= s32\[(\d+)\]\S* (gather|scatter)\(.*"
                      r'op_name="[^"]*/ddm\.ranks/')


@pytest.mark.parametrize("form,max_pairs", MESH_FORMS)
def test_mesh_emission_reads_each_opening_rank_with_one_gather(
        topo, form, max_pairs):
    """The mesh emission's ranks stage, compiled for a v5e:2x2, gathers
    once per stream record (each emitter's opening rank from the join
    table) and builds its two tables with at most three scatters: no
    per-extent table is gathered back at the records that wrote it."""
    _, emit, shard = _mesh_programs(topo, form, max_pairs, n=4096, m=3072)
    ops = [(kind, int(size)) for size, kind in
           RANKS_OP.findall(emit.compile().as_text())]
    gathers = [size for kind, size in ops if kind == "gather"]
    assert gathers == [shard], ops
    assert 1 <= sum(kind == "scatter" for kind, _ in ops) <= 3, ops
