"""Compile the Pallas engines for a described TPU v5e, at the
paper's default width (n = 2^20, L = 64), with the block sizes their
plans pick. Nothing runs: the TPU compiler is installed and compiles
for a chip that is described, not attached, so a Mosaic refusal (an
illegal slice, a scalar stored to VMEM, more VMEM or SMEM than the
kernel may use) fails here instead of on the chip.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library at a time, and every test
worker imports this file.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import EdgeStream, SubstreamConfig
from repro.graph.waves import block_aligned_layout, wave_schedule
from repro.kernels.substream_match import ops

N = 2**20
L = 64
CFG = SubstreamConfig(n=N, L=L, eps=0.1)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """Described-chip compiles cannot be read back from the persistent
    cache, so keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _independent_schedule(m):
    """A conflict-free stream: one wave, every segment full."""
    return wave_schedule(np.arange(0, 2 * m, 2), np.arange(1, 2 * m, 2))


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def _compile_edges(cfg, block_e, m, sharding, packed=True, mb0=False):
    stream = EdgeStream(
        src=_spec((m,), jnp.int32, sharding),
        dst=_spec((m,), jnp.int32, sharding),
        weight=_spec((m,), jnp.float32, sharding),
        valid=_spec((m,), jnp.bool_, sharding),
    )
    words = ops.vmem_plan(cfg.n, cfg.L, packed=packed).words
    mb0_spec = (
        _spec((cfg.n, words), jnp.uint8 if packed else jnp.bool_, sharding)
        if mb0 else None
    )
    return ops._substream_match_edges.lower(
        stream, cfg, block_e=block_e, interpret=False, packed=packed,
        mb0=mb0_spec,
    ).compile()


def _compile_slots(cfg, plan, group, sharding, programs=2, mb0=False):
    total = programs * plan.block_e
    words = plan.words
    args = [_spec((total,), jnp.int32, sharding)] * 2 + [
        _spec((total,), jnp.float32, sharding),
        _spec((), jnp.int32, sharding),
    ]
    mb0_spec = _spec((cfg.n, words), jnp.uint8, sharding) if mb0 else None
    return ops._slots_device.lower(
        *args, cfg, ops._kernel_plan(plan), group, False, mb0=mb0_spec
    ).compile()


@pytest.mark.parametrize("mb0", [False, True], ids=["zero", "carried"])
def test_edges_compiles_at_paper_width(one_chip, mb0):
    plan = ops.vmem_plan(N, L, m=4 * ops.MAX_BLOCK)
    assert plan.block_e == ops.MAX_BLOCK
    compiled = _compile_edges(
        CFG, None, 4 * ops.MAX_BLOCK, one_chip, mb0=mb0
    )
    _assert_kernel(compiled)


def test_waves_compiles_at_paper_width(one_chip):
    """The megakernel at one segment per tile, the fallback ladder's
    second rung."""
    sch = _independent_schedule(2**16)
    plan = ops.mega_plan(N, L, block_aligned_layout(sch, 1))
    assert plan.block_e % ops.SMEM_ALIGN == 0
    _assert_kernel(_compile_slots(CFG, plan, plan.seg, one_chip))


@pytest.mark.parametrize("mb0", [False, True], ids=["zero", "carried"])
def test_mega_compiles_at_paper_width(one_chip, mb0):
    sch = _independent_schedule(2**16)
    layout = block_aligned_layout(sch, ops.MEGA_SEG_BLOCK)
    plan = ops.mega_plan(N, L, layout)
    assert plan.block_e % ops.SMEM_ALIGN == 0
    compiled = _compile_slots(
        CFG, plan, plan.seg * plan.seg_block, one_chip, mb0=mb0
    )
    _assert_kernel(compiled)


@pytest.mark.parametrize("packed", [True, False], ids=["packed", "unpacked"])
def test_max_vertices_plan_compiles(one_chip, packed):
    """The largest block the plan admits is one the compiler accepts
    under the plan's own vmem_limit_bytes."""
    n = ops.max_vertices(L, packed=packed)
    plan = ops.vmem_plan(n, L, packed=packed, m=ops.MAX_BLOCK)
    assert plan.nbytes <= ops.VMEM_BIT_BUDGET
    assert ops.vmem_plan(n + plan.n_pad // plan.rows * 8, L, packed=packed).nbytes > (
        ops.VMEM_BIT_BUDGET
    )
    cfg = SubstreamConfig(n=n, L=L, eps=0.1)
    _assert_kernel(
        _compile_edges(cfg, None, ops.MAX_BLOCK, one_chip, packed=packed)
    )


def test_mega_device_memory_does_not_grow_with_padding(one_chip):
    """The slot streams reach the kernel as three flat int32 arrays, so
    the device program's scratch does not grow with the slot count. An
    interleaved ``[total, 3]`` stream was tiled (8, 128) on its way to
    one flat array, 512 B a slot: about 24 GB for the 46.7 M slots of a
    scale-20 Kronecker job, more than the chip's 16 GB."""
    sch = _independent_schedule(2**16)
    layout = block_aligned_layout(sch, ops.MEGA_SEG_BLOCK)
    plan = ops.mega_plan(N, L, layout)
    group = plan.seg * plan.seg_block
    small, large = (
        _compile_slots(CFG, plan, group, one_chip, programs=p).memory_analysis()
        for p in (2, 512)
    )
    slots = (512 - 2) * plan.block_e
    assert large.temp_size_in_bytes - small.temp_size_in_bytes < 4 * slots
    # u, v, w in: 12 B a slot
    assert large.argument_size_in_bytes - small.argument_size_in_bytes == 12 * slots


def test_links_program_fits_at_paper_scale(one_chip):
    """The wave schedule's conflict links at the bucket of the paper's
    scale-20 Kronecker graph (m = 44,350,400; 92 M link entries): two
    sorts over flat int32 arrays, whose arguments and scratch stay under
    half of the chip's 16 GB beside the stream it already holds."""
    from repro.graph import waves

    entries = waves.link_bucket(2 * 44_350_400)
    vert = _spec((entries,), jnp.int32, one_chip)
    stats = waves._links_device.lower(vert).compile().memory_analysis()
    assert stats.argument_size_in_bytes + stats.temp_size_in_bytes < 8e9
    # succ and waiting come back flat: 12 B a rank, no (8, 128) tiling
    assert stats.output_size_in_bytes < 12.1 * (entries // 2)
