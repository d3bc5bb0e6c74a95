"""Pallas substream_match kernel: shape/dtype sweeps vs the jnp oracle,
packed (uint8 bit-plane) vs unpacked (bool) layout parity, and the
VMEM plan contract."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    EdgeStream,
    SubstreamConfig,
    mwm_scan,
    pack_bits,
    packed_width,
    unpack_bits,
)
from repro.kernels.substream_match.ops import (
    VMEM_BIT_BUDGET,
    max_vertices,
    substream_match,
    vmem_plan,
)
from repro.kernels.substream_match.ref import (
    substream_match_ref,
    substream_match_ref_packed,
)


def _case(n, m, L, eps, seed, wdtype=np.float32, pad=0):
    rng = np.random.default_rng(seed)
    cfg = SubstreamConfig(n=n, L=L, eps=eps)
    src = rng.integers(0, n, m)
    dst = rng.integers(0, n, m)  # self-loops kept on purpose
    w = rng.uniform(0.5, cfg.w_max * 1.05, m).astype(wdtype)
    return EdgeStream.from_numpy(src, dst, w, n_pad=m + pad), cfg


@pytest.mark.parametrize("packed", [True, False], ids=["packed", "unpacked"])
@pytest.mark.parametrize("n,m,L,block_e", [
    (16, 40, 1, 8),
    (100, 500, 48, 128),
    (64, 256, 64, 64),
    (257, 1000, 17, 256),  # unaligned n and L (L % 8 != 0)
    (32, 7, 128, 8),  # fewer edges than one block
])
def test_kernel_matches_scan(n, m, L, block_e, packed):
    stream, cfg = _case(n, m, L, 0.15, seed=n + m)
    want = mwm_scan(stream, cfg)
    got = substream_match(stream, cfg, block_e=block_e, interpret=True, packed=packed)
    assert got.is_packed == packed
    assert (np.asarray(got.assigned) == np.asarray(want.assigned)).all()
    assert (np.asarray(got.mb) == np.asarray(want.mb)).all()


@pytest.mark.parametrize("L", [1, 7, 9, 33, 64])
def test_packed_unpacked_parity(L):
    """Bit-identical assigned + mb across layouts, incl. L % 8 != 0,
    self-loops (kept by _case) and padding edges."""
    stream, cfg = _case(48, 300, L, 0.2, seed=L, pad=29)
    got_p = substream_match(stream, cfg, block_e=64, interpret=True, packed=True)
    got_u = substream_match(stream, cfg, block_e=64, interpret=True, packed=False)
    assert (np.asarray(got_p.assigned) == np.asarray(got_u.assigned)).all()
    assert (np.asarray(got_p.mb) == np.asarray(got_u.mb)).all()
    # the packed words match an independent host-side pack of the dense bits
    assert (np.asarray(got_p.mb_packed) == np.asarray(pack_bits(got_u.mb))).all()
    assert got_p.mb_packed.shape == (cfg.n, packed_width(L))


def test_layout_follows_config_flag():
    stream, cfg = _case(20, 50, 12, 0.1, seed=5)
    assert substream_match(stream, cfg, block_e=16).is_packed
    cfg_u = SubstreamConfig(n=20, L=12, eps=0.1, mb_layout="unpacked")
    assert not substream_match(stream, cfg_u, block_e=16).is_packed
    cfg_typo = SubstreamConfig(n=20, L=12, eps=0.1, mb_layout="packd")
    with pytest.raises(ValueError, match="mb_layout"):
        substream_match(stream, cfg_typo, block_e=16)


@pytest.mark.parametrize("wdtype", [np.float32, np.float16])
def test_kernel_weight_dtypes(wdtype):
    stream, cfg = _case(48, 300, 32, 0.2, seed=7, wdtype=wdtype)
    want = mwm_scan(stream, cfg)
    got = substream_match(stream, cfg, block_e=64, interpret=True)
    assert (np.asarray(got.assigned) == np.asarray(want.assigned)).all()


def test_kernel_padding_edges():
    stream, cfg = _case(30, 100, 16, 0.1, seed=3, pad=57)
    want = mwm_scan(stream, cfg)
    got = substream_match(stream, cfg, block_e=32, interpret=True)
    assert (np.asarray(got.assigned) == np.asarray(want.assigned)).all()


def test_kernel_ref_oracle_agrees():
    stream, cfg = _case(40, 200, 24, 0.1, seed=11)
    w = jnp.where(stream.valid, stream.weight, 0.0)
    a_ref, mb_ref = substream_match_ref(
        stream.src, stream.dst, w, cfg.thresholds(), cfg.n
    )
    want = mwm_scan(stream, cfg)
    assert (np.asarray(a_ref) == np.asarray(want.assigned)).all()
    assert (np.asarray(mb_ref).astype(bool) == np.asarray(want.mb)).all()


@pytest.mark.parametrize("L", [3, 24, 33])
def test_kernel_packed_ref_oracle_agrees(L):
    """The independent packed-word scan oracle reproduces the dense oracle."""
    stream, cfg = _case(40, 200, L, 0.1, seed=11)
    w = jnp.where(stream.valid, stream.weight, 0.0)
    a_ref, mbp_ref = substream_match_ref_packed(
        stream.src, stream.dst, w, cfg.thresholds(), cfg.n
    )
    want = mwm_scan(stream, cfg)
    assert (np.asarray(a_ref) == np.asarray(want.assigned)).all()
    assert (np.asarray(unpack_bits(mbp_ref, cfg.L)) == np.asarray(want.mb)).all()


@pytest.mark.parametrize("packed", [True, False], ids=["packed", "unpacked"])
def test_vmem_budget_enforced(packed):
    cfg = SubstreamConfig(n=100_000_000, L=512, eps=0.1)
    stream, _ = _case(16, 8, 4, 0.1, seed=0)
    with pytest.raises(ValueError, match="VMEM"):
        substream_match(stream, cfg, interpret=True, packed=packed)


def test_vmem_plan_alignment():
    """The plan counts the bit block as the compiler tiles it: int32 rows
    of 128 lanes in 8-row tiles, vertices folded into lane ranges. At a
    few hundred vertices the 8-row tile dominates, so the layout ratio
    is checked where it does not."""
    for packed in (True, False):
        small = vmem_plan(100, 48, packed=packed)
        assert small.rows % 8 == 0 and small.row_width % 128 == 0
        assert small.nbytes == small.rows * small.row_width * 4
        assert small.n_pad >= 100
    plan_u = vmem_plan(100_000, 48, packed=False)
    assert plan_u.n_pad % 8 == 0 and plan_u.width % 128 == 0
    assert plan_u.nbytes == plan_u.n_pad * plan_u.width
    plan_p = vmem_plan(100_000, 48, packed=True)
    assert plan_p.n_pad % 8 == 0 and plan_p.width % 8 == 0
    assert plan_p.words == packed_width(48) == 6
    assert plan_p.nbytes == plan_p.n_pad * plan_p.width
    assert plan_p.nbytes * 8 <= plan_u.nbytes


def test_vmem_plan_packed_capacity_8x():
    """Acceptance: >= 8x more vertices per core at L=64 (16x: lane padding)."""
    cap_p = max_vertices(64, packed=True)
    cap_u = max_vertices(64, packed=False)
    assert cap_p >= 8 * cap_u
    assert vmem_plan(cap_p, 64, packed=True).nbytes <= VMEM_BIT_BUDGET


def test_vmem_plan_auto_block_e():
    plan = vmem_plan(1000, 64)
    assert plan.block_e >= 128 and plan.block_e & (plan.block_e - 1) == 0
    # the bit block never starves the edge buffers (>= 4 MiB stays free),
    # so without a stream length the 8192 latency cap decides
    assert plan.block_e == 8192
    # short streams are not padded to the cap: block_e covers m snugly
    assert vmem_plan(1000, 64, m=50).block_e == 128
    assert vmem_plan(1000, 64, m=700).block_e == 1024
    assert vmem_plan(1000, 64, m=100_000).block_e == 8192


def test_auto_block_e_small_stream_end_to_end():
    """Default block_e on a tiny stream stays tiny (no 8192-pad blowup)."""
    stream, cfg = _case(16, 20, 8, 0.1, seed=2)
    want = mwm_scan(stream, cfg)
    got = substream_match(stream, cfg)  # auto block_e
    assert (np.asarray(got.assigned) == np.asarray(want.assigned)).all()
    assert (np.asarray(got.mb) == np.asarray(want.mb)).all()


def test_matching_result_requires_L_for_packed():
    from repro.core import MatchingResult

    packed = pack_bits(jnp.zeros((4, 17), bool))
    with pytest.raises(ValueError, match="L is required"):
        MatchingResult(assigned=jnp.zeros(3, jnp.int32), mb_packed=packed)
    ok = MatchingResult(assigned=jnp.zeros(3, jnp.int32), mb_packed=packed, L=17)
    assert ok.mb.shape == (4, 17)


@pytest.mark.parametrize("L", [1, 8, 13, 64])
def test_bitpack_roundtrip(L):
    rng = np.random.default_rng(L)
    mb = rng.integers(0, 2, (37, L)).astype(bool)
    packed = pack_bits(jnp.asarray(mb))
    assert packed.dtype == jnp.uint8
    assert packed.shape == (37, packed_width(L))
    assert (np.asarray(unpack_bits(packed, L)) == mb).all()
    # padding bits of the last byte stay zero
    if L % 8:
        assert not (np.asarray(packed[:, -1]) >> (L % 8)).any()
