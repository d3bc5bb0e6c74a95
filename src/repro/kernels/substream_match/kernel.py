"""Pallas TPU kernel: the paper's 8-stage edge-processor pipeline (§4.4).

TPU mapping of the FPGA design:

  FPGA                                   TPU (this kernel)
  ------------------------------------   --------------------------------
  BRAM-resident matching bits            resident VMEM output block,
                                         int32 [rows, 128], G vertices
                                         folded into each 128-lane row
  L-bit bit-parallel matching word       ``lanes`` int32 words per vertex
                                         (32 substreams per word packed,
                                         1 per word unpacked)
  1 edge / cycle pipeline                lax.fori_loop over slot groups
  DRAM edge stream + prefetch            HBM->SMEM BlockSpec pipeline over
                                         slot blocks (double-buffered by
                                         the Pallas grid pipeline)

One kernel body serves both engines. A *slot* is ``(u, v, cnt)``:
the two endpoints and the number of substream thresholds the weight
passes. Thresholds ``(1+eps)^i`` are sorted, so the Stage-4 eligibility
word of an edge is the prefix of its lowest ``cnt`` substreams; the
caller computes ``cnt`` (0 for self-loops, padding and invalid edges).
Slots come in *groups* whose members are vertex-disjoint: group size 1
is the paper's per-edge processor (``edges``), and a block-aligned
tile of ``seg_block * seg`` slots, a subset of one wave, is the
megakernel (``mega``). Disjointness
lets a group load all its rows before it stores any: no slot of the
group can see another's update, so the result is the sequential one.

Stage map (Listing 2): Stage 1 = scalar slot reads from SMEM and the
row address ``u // G``; Stage 2-3 = dynamic single-row loads; Stage 4 =
the prefix word from ``cnt`` (an iota compare, no table); Stage 5 =
``te & ~mb[u] & ~mb[v]``; Stage 6 = read-modify-write OR of the new
bits into both rows; Stage 7 = highest set bit via ``clz``; Stage 8 =
a scalar store of the substream index into the SMEM ``assigned`` block.

Layout. Mosaic tiles 32-bit data as (8, 128), so a one-row dynamic
load is legal for int32 and not for 8-bit types. Each vertex owns
``lanes`` consecutive int32 lanes of a 128-lane row; ``G = 128 //
lanes`` vertices share a row, and vertex ``u`` lives in row ``u // G``
at lane offset ``(u % G) * lanes``. The ``v`` row is rotated onto
``u``'s lanes for Stage 5 and the new bits are rotated back for Stage 6.
Vertices wider than 128 lanes (G = 1) use ``lanes`` (a multiple of 128)
as the row width and need no rotation.

Grid: one program per slot block, sequential ("arbitrary"), so the
resident output block carries the bit state across programs and the
stream order is preserved. ``bound`` (scalar prefetch) is the number of
real groups; programs stop there, so grid padding costs no trips.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Lanes in one vector row of the TPU.
LANES = 128
#: Int32 words per slot in the SMEM slot streams: one each of u, v, cnt.
SLOT_WORDS = 3


def _prefix_words(cnt, k, bits: int):
    """Eligibility word of lane ``k`` (vertex-relative) for a slot that
    passes ``cnt`` thresholds: the lowest ``clip(cnt - bits*k, 0, bits)``
    bits set. ``bits`` is 32 (packed words) or 1 (one substream per
    word)."""
    nb = jnp.clip(cnt - bits * k, 0, bits)
    if bits == 1:
        return nb
    return jnp.where(nb >= 32, -1, (1 << jnp.minimum(nb, 31)) - 1)


def _kernel(
    bound_ref, u_ref, v_ref, cnt_ref, *refs,
    group: int, groups_per_block: int, lanes: int, bits: int,
):
    if len(refs) == 4:
        mb0_ref, assigned_ref, mb_ref, sem = refs
    else:
        (assigned_ref, mb_ref), mb0_ref = refs, None
    b = pl.program_id(0)

    @pl.when(b == 0)
    def _init():
        if mb0_ref is None:
            mb_ref[...] = jnp.zeros_like(mb_ref)
        else:
            copy = pltpu.make_async_copy(mb0_ref, mb_ref, sem)
            copy.start()
            copy.wait()

    width = mb_ref.shape[1]
    fold = width // lanes
    shift = fold.bit_length() - 1
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    ngroups = jnp.clip(bound_ref[0] - b * groups_per_block, 0, groups_per_block)

    def locate(x):
        # vertex -> (row, lane offset of its first word)
        if fold == 1:
            return x, 0
        return x >> shift, (x & (fold - 1)) * lanes

    def rotate(row, amount):
        if fold == 1:
            return row
        return pltpu.roll(row, amount & (width - 1), 1)

    def body(t, carry):
        writes = []
        for j in range(group):
            s = t * group + j
            # Stage 1: slot scalars and row addresses
            u = u_ref[s]
            v = v_ref[s]
            cnt = cnt_ref[s]
            ru, ou = locate(u)
            rv, ov = locate(v)
            # Stage 2-3: single-row loads; v's words rotated onto u's lanes
            mbu = mb_ref[pl.ds(ru, 1), :]
            mbv = rotate(mb_ref[pl.ds(rv, 1), :], ou - ov)
            # Stage 4: the eligibility prefix on u's lanes only
            k = lane - ou
            te = jnp.where(
                (k >= 0) & (k < lanes), _prefix_words(cnt, k, bits), 0
            )
            # Stage 5: the matching update, 32 substreams per word
            add = te & ~mbu & ~mbv
            # Stage 7: highest set bit over the vertex's words
            high = bits * k + (31 - jax.lax.clz(add))
            # Stage 8: emit the assignment
            assigned_ref[s] = jnp.max(jnp.where(add != 0, high, -1))
            writes.append((ru, add, rv, rotate(add, ov - ou)))
        # Stage 6: OR the new bits into both rows (u first, then v, so a
        # shared row sees both updates)
        for ru, add_u, rv, add_v in writes:
            mb_ref[pl.ds(ru, 1), :] = mb_ref[pl.ds(ru, 1), :] | add_u
            mb_ref[pl.ds(rv, 1), :] = mb_ref[pl.ds(rv, 1), :] | add_v
        return carry

    jax.lax.fori_loop(0, ngroups, body, 0)


def substream_match_pallas(
    u: jax.Array,  # int32 [total]: first endpoint per slot
    v: jax.Array,  # int32 [total]: second endpoint per slot
    cnt: jax.Array,  # int32 [total]: thresholds the slot's weight passes
    num_groups: jax.Array,  # int32 [1]: real groups; trips stop there
    rows: int,
    width: int,
    lanes: int,
    bits: int,
    group: int,
    groups_per_block: int,
    vmem_limit: int,
    interpret: bool = False,
    mb_init: jax.Array | None = None,  # int32 [rows, width] carried-in bits
):
    """Raw pallas_call wrapper: run the slot stream over a folded bit block.

    ``total`` slots form ``total / (group * groups_per_block)`` grid
    programs; each program's block of ``u``, ``v`` and ``cnt`` is one
    SMEM block per stream. The three are separate flat arrays: an
    interleaved ``[total, 3]`` stream would be tiled (8, 128) in HBM on
    its way to one flat array, 128 lanes for 3 words, about 512 B a
    slot. Within a group the slots must be vertex-disjoint (any slot
    with ``cnt = 0`` may alias a vertex: it changes nothing). Returns
    ``(assigned int32 [total], mb int32 [rows, width])``; assigned is -1
    where nothing matched and undefined past ``num_groups`` groups.
    ``mb_init`` seeds the bit block (copied from HBM once); ``None``
    zero-fills it.
    """
    block = group * groups_per_block
    total = u.shape[0]
    assert v.shape == cnt.shape == (total,), (u.shape, v.shape, cnt.shape)
    assert total % block == 0, (total, group, groups_per_block)
    smem = pltpu.MemorySpace.SMEM
    in_specs = [
        pl.BlockSpec((block,), lambda b, bound: (b,), memory_space=smem)
    ] * SLOT_WORDS
    operands = [num_groups, u, v, cnt]
    scratch = []
    if mb_init is not None:
        assert mb_init.shape == (rows, width), (mb_init.shape, rows, width)
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        operands.append(mb_init.astype(jnp.int32))
        scratch.append(pltpu.SemaphoreType.DMA(()))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(total // block,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((block,), lambda b, bound: (b,), memory_space=smem),
            pl.BlockSpec((rows, width), lambda b, bound: (0, 0)),
        ],
        scratch_shapes=scratch,
    )
    kernel = functools.partial(
        _kernel, group=group, groups_per_block=groups_per_block,
        lanes=lanes, bits=bits,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((total,), jnp.int32),
            jax.ShapeDtypeStruct((rows, width), jnp.int32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_limit,
        ),
        name="substream_match",
    )(*operands)
