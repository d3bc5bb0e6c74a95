"""Property-based invariants of the grid-pipelined segment megakernel.

Random RMAT graphs with random ``seg_block`` / ``L`` draws
must leave the megakernel bit-identical to the sequential scan, and the
double-buffered grid pipeline must never let a tile's gather observe
state from its own (or a later) tile's scatter.  The second property is
checked two ways:

* structurally — in the block-aligned layout no tile straddles a wave
  boundary and every tile is vertex-disjoint, so a one-tile-op
  gather/compute/scatter cannot race itself;
* behaviourally — a host replay that processes one tile per step,
  reading **only pre-tile state** for the whole tile, reproduces the
  interpret-mode kernel exactly.  If any double-buffered trip read a
  segment tile before the previous tile's scatter landed, the kernel
  would diverge from this replay (and from the scan).
"""
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import EdgeStream, SubstreamConfig, mwm_scan
from repro.graph.generators import kronecker_graph, uniform_weights
from repro.graph.waves import block_aligned_layout, wave_schedule
from repro.kernels.substream_match.ops import substream_match

SETTINGS = dict(max_examples=12, deadline=None)


def _rmat_case(draw):
    scale = draw(st.integers(3, 6))
    ef = draw(st.sampled_from([1, 2, 4]))
    L = draw(st.sampled_from([1, 9, 16, 33]))
    seed = draw(st.integers(0, 2**31 - 1))
    src, dst = kronecker_graph(scale, edge_factor=ef, seed=seed)
    n = 1 << scale
    cfg = SubstreamConfig(n=n, L=L, eps=0.1)
    w = uniform_weights(src.shape[0], L, 0.1, seed=seed).astype(np.float32)
    pad = draw(st.sampled_from([0, 5]))
    stream = EdgeStream.from_numpy(src, dst, w, n_pad=src.shape[0] + pad)
    seg_block = draw(st.sampled_from([1, 2, 3, 4]))
    return stream, cfg, seg_block


@given(st.data())
@settings(**SETTINGS)
def test_mega_bit_identical_to_scan(data):
    """Random RMAT x random seg_block/L: mega == scan, bit for bit,
    in both bit layouts."""
    stream, cfg, seg_block = _rmat_case(data.draw)
    want = mwm_scan(stream, cfg)
    packed = data.draw(st.booleans())
    got = substream_match(
        stream,
        cfg,
        schedule="mega",
        seg_block=seg_block,
        interpret=True,
        packed=packed,
    )
    assert got.is_packed == packed
    assert (np.asarray(got.assigned) == np.asarray(want.assigned)).all()
    assert (np.asarray(got.mb) == np.asarray(want.mb)).all()


def _tile_replay(layout, stream, cfg):
    """Host oracle of the pipelined tile semantics: one tile per step,
    the whole tile reads only pre-tile state, then scatters atomically.
    Well-defined only because tiles are vertex-disjoint."""
    src = np.asarray(stream.src)
    dst = np.asarray(stream.dst)
    w = np.where(np.asarray(stream.valid), np.asarray(stream.weight), 0.0)
    thr = np.asarray(cfg.thresholds())
    L = cfg.L
    mb = np.zeros((cfg.n, L), bool)
    assigned = np.full(src.shape[0], -1, np.int32)
    sb = layout.seg_block
    for t in range(layout.num_tiles):
        rows = layout.slots[t * sb : (t + 1) * sb].reshape(-1)
        pos = rows[rows >= 0]
        u, v, wt = src[pos], dst[pos], w[pos]
        te = (wt[:, None] >= thr[None, :]) & (u != v)[:, None]
        add = te & ~mb[u] & ~mb[v]  # pre-tile state only
        mb[u] |= add
        mb[v] |= add
        hit = add.any(axis=1)
        assigned[pos] = np.where(
            hit, L - 1 - np.argmax(add[:, ::-1], axis=1), -1
        )
    return assigned, mb


@given(st.data())
@settings(**SETTINGS)
def test_mega_tiles_never_read_before_scatter(data):
    """Double-buffer safety: (a) no tile straddles a wave boundary and
    every tile is vertex-disjoint (structural race-freedom), (b) the
    interpret-mode kernel equals the atomic pre-tile-state replay."""
    stream, cfg, seg_block = _rmat_case(data.draw)
    src = np.asarray(stream.src)
    dst = np.asarray(stream.dst)
    valid = np.asarray(stream.valid)
    sch = wave_schedule(src, dst, valid=valid)
    layout = block_aligned_layout(sch, seg_block)
    # (a) structural: each tile lies inside one wave...
    offs = layout.seg_offsets
    sb = layout.seg_block
    for t in range(layout.num_tiles):
        lo, hi = t * sb, (t + 1) * sb
        wave_lo = np.searchsorted(offs, lo, side="right") - 1
        assert offs[wave_lo] <= lo and hi <= offs[wave_lo + 1], (
            f"tile {t} straddles a wave boundary"
        )
        # ...and is vertex-disjoint, so its one-op scatter cannot race
        rows = layout.slots[lo:hi].reshape(-1)
        pos = rows[rows >= 0]
        live = pos[src[pos] != dst[pos]]
        verts = np.concatenate([src[live], dst[live]])
        assert len(verts) == len(set(verts.tolist())), f"tile {t} conflict"
    # (b) behavioural: kernel == atomic tile replay == scan
    want_a, want_mb = _tile_replay(layout, stream, cfg)
    got = substream_match(
        stream, cfg, schedule="mega", waves=sch, seg_block=seg_block,
        interpret=True, packed=False,
    )
    assert (np.asarray(got.assigned) == want_a).all()
    assert (np.asarray(got.mb) == want_mb).all()
    ref = mwm_scan(stream, cfg)
    assert (want_a == np.asarray(ref.assigned)).all()
    assert (want_mb == np.asarray(ref.mb)).all()
