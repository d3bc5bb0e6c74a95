"""Core data types for the substream-centric matching framework.

An edge stream is a struct-of-arrays: ``src[i], dst[i], weight[i]`` in
*stream order* (the order the paper's FPGA would receive them). All
algorithms in :mod:`repro.core` treat the stream order as the greedy
priority order, exactly like Listing 1 of the paper.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitpack

_I32 = np.iinfo(np.int32)


def _int32_cast_faults(a: np.ndarray) -> np.ndarray:
    """bool mask: True where ``a.astype(np.int32)`` would change the value."""
    if a.dtype == np.int32 or a.dtype == bool:
        return np.zeros(a.shape, bool)
    if np.issubdtype(a.dtype, np.integer):
        return (a < _I32.min) | (a > _I32.max)
    if np.issubdtype(a.dtype, np.floating):
        with np.errstate(invalid="ignore"):
            bad = ~np.isfinite(a) | (a < _I32.min) | (a > _I32.max)
            frac = np.zeros(a.shape, bool)
            ok = ~bad
            frac[ok] = a[ok] != np.trunc(a[ok])
        return bad | frac
    try:  # exotic dtypes (object arrays of python ints): round-trip via int64
        a64 = a.astype(np.int64)
    except (TypeError, ValueError, OverflowError):
        return np.ones(a.shape, bool)
    return (a64 < _I32.min) | (a64 > _I32.max)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class EdgeStream:
    """A weighted edge stream. ``src``/``dst`` are int32 [m], ``weight`` f32 [m].

    ``valid`` masks padding edges (False entries are ignored by every
    matcher); padding lets us keep shapes static under jit/shard_map.
    """

    src: jax.Array
    dst: jax.Array
    weight: jax.Array
    valid: jax.Array  # bool [m]

    @property
    def num_edges(self) -> int:
        return self.src.shape[0]

    @staticmethod
    def from_numpy(
        src, dst, weight, n_pad: Optional[int] = None, policy: str = "strict"
    ) -> "EdgeStream":
        """Build a stream from host arrays, guarding the narrowing casts.

        The int32/float32 casts can silently destroy data: an int64
        vertex id wraps modulo 2^32, a float64 weight overflows to Inf.
        ``policy`` controls what happens to entries the casts cannot
        represent (ids outside int32, weights non-finite after the
        float32 cast):

        * ``"strict"`` (default) — raise a structured
          :class:`repro.core.guard.StreamValidationError` naming the
          offending positions;
        * ``"sanitize"`` — drop those edges (``valid=False``, slots
          zeroed like padding);
        * ``"off"`` — the legacy wrap/NaN-propagate cast, for callers
          that have already validated.

        Range checks against ``n`` (ids in ``[0, n)``, negative/NaN
        weights) are :func:`repro.core.guard.validate_stream`'s job —
        this only guards representability of the casts themselves.
        """
        if policy not in ("strict", "sanitize", "off"):
            raise ValueError(
                f"unknown policy {policy!r}; use 'strict', 'sanitize' or 'off'"
            )
        src_in = np.asarray(src)
        dst_in = np.asarray(dst)
        w_in = np.asarray(weight)
        m = src_in.shape[0]
        if dst_in.shape[0] != m or w_in.shape[0] != m:
            raise ValueError(
                f"src/dst/weight lengths differ: "
                f"{m}/{dst_in.shape[0]}/{w_in.shape[0]}"
            )
        drop = np.zeros(m, bool)
        if policy != "off" and m:
            from repro.core import guard  # deferred: guard imports this module

            bad_id = _int32_cast_faults(src_in) | _int32_cast_faults(dst_in)
            with np.errstate(invalid="ignore", over="ignore"):
                bad_w = ~np.isfinite(w_in.astype(np.float32))
            problems = [
                guard._problem(kind, mask, detail=detail)
                for kind, mask, detail in (
                    ("id_overflow", bad_id, "vertex id not representable as int32"),
                    ("nonfinite_weight", bad_w, "weight non-finite after the float32 cast"),
                )
                if mask.any()
            ]
            if problems:
                if policy == "strict":
                    raise guard.StreamValidationError(problems)
                drop = bad_id | bad_w
        with np.errstate(invalid="ignore", over="ignore"):
            src_np = np.where(drop, 0, src_in).astype(np.int32)
            dst_np = np.where(drop, 0, dst_in).astype(np.int32)
            w_np = np.where(drop, 0.0, w_in).astype(np.float32)
        m_pad = m if n_pad is None else n_pad
        if m_pad < m:
            raise ValueError(f"pad {m_pad} < m {m}")
        pad = m_pad - m
        valid = np.concatenate([~drop, np.zeros(pad, bool)])
        z = np.zeros(pad, np.int32)
        return EdgeStream(
            src=jnp.asarray(np.concatenate([src_np, z])),
            dst=jnp.asarray(np.concatenate([dst_np, z])),
            weight=jnp.asarray(np.concatenate([w_np, np.zeros(pad, np.float32)])),
            valid=jnp.asarray(valid),
        )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SubstreamConfig:
    """Parameters of the Crouch–Stubbs reduction.

    ``L`` substreams; substream ``i`` admits edges with
    ``w >= (1 + eps)**i``. The paper selects ``eps`` per L
    (Fig. 11 caption); we expose both knobs.
    """

    n: int = dataclasses.field(metadata=dict(static=True))
    L: int = dataclasses.field(metadata=dict(static=True))
    eps: float = dataclasses.field(default=0.1, metadata=dict(static=True))
    # Matching-bit storage layout: "packed" (uint8 bit planes, the §4.3
    # BRAM-word analogue — 8x the VMEM capacity) or "unpacked" (one int8
    # per bit; the legacy fallback). Consumed by kernels/substream_match.
    mb_layout: str = dataclasses.field(default="packed", metadata=dict(static=True))

    def thresholds(self) -> jax.Array:
        """[L] float32 substream admission thresholds (1+eps)^i.

        Computed on the host in float64 and rounded once, so a jitted
        engine, an eager check and every backend compare against the
        same float32 values (a device ``pow`` differs by an ulp between
        fused and eager evaluation on a TPU)."""
        i = np.arange(self.L, dtype=np.float64)
        return jnp.asarray(((1.0 + self.eps) ** i).astype(np.float32))

    @property
    def w_max(self) -> float:
        return float((1.0 + self.eps) ** self.L)


class MatchingResult:
    """Output of Part 1 (stream processing).

    ``assigned`` int32 [m]: the substream index whose list ``C[i]`` records
    the edge (the *highest* eligible substream where both endpoints were
    free), or -1 if the edge entered no list.

    The matching bits are held in ONE of two storages:

    * ``mb`` bool [n, L] — the dense view every pre-existing caller reads;
    * ``mb_packed`` uint8 [n, ceil(L/8)] — the bit-plane layout of
      :mod:`repro.core.bitpack` (the paper's §4.3 BRAM word).

    ``.mb`` is always readable: when only the packed storage is present it
    is unpacked lazily on access (outside any jit), so packed producers
    don't break dense consumers. ``.packed()`` is the mirror-image accessor.
    ``L`` (static) records the logical substream count; it is required to
    trim the last byte's padding bits when unpacking.
    """

    __slots__ = ("assigned", "_mb", "_mb_packed", "_L")

    def __init__(self, assigned, mb=None, mb_packed=None, L=None):
        if mb is None and mb_packed is None:
            raise ValueError("MatchingResult needs mb or mb_packed")
        if L is None:
            if mb is None:
                # W*8 would silently invent up to 7 phantom substreams
                raise ValueError(
                    "L is required when only mb_packed is given "
                    "(the packed width cannot recover L when L % 8 != 0)"
                )
            L = mb.shape[-1]
        object.__setattr__(self, "assigned", assigned)
        object.__setattr__(self, "_mb", mb)
        object.__setattr__(self, "_mb_packed", mb_packed)
        object.__setattr__(self, "_L", int(L))

    def __setattr__(self, name, value):  # immutable, like the old frozen dataclass
        raise dataclasses.FrozenInstanceError(f"cannot assign to field {name!r}")

    @property
    def L(self) -> int:
        return self._L

    @property
    def mb(self) -> jax.Array:
        """bool [n, L] dense matching bits (lazily unpacked if packed)."""
        if self._mb is not None:
            return self._mb if self._mb.dtype == bool else self._mb.astype(bool)
        return bitpack.unpack_bits(self._mb_packed, self._L)

    @property
    def mb_packed(self) -> Optional[jax.Array]:
        """uint8 [n, ceil(L/8)] packed storage, or None if produced dense."""
        return self._mb_packed

    @property
    def is_packed(self) -> bool:
        return self._mb_packed is not None

    def packed(self) -> jax.Array:
        """uint8 [n, ceil(L/8)] packed bits (packing the dense view if needed)."""
        if self._mb_packed is not None:
            return self._mb_packed
        return bitpack.pack_bits(self.mb)

    def with_assigned(self, assigned) -> "MatchingResult":
        """Same bit storage, different ``assigned`` (e.g. un-permuted)."""
        return MatchingResult(
            assigned, mb=self._mb, mb_packed=self._mb_packed, L=self._L
        )

    def __repr__(self) -> str:
        store = "packed" if self.is_packed else "dense"
        return f"MatchingResult(assigned={self.assigned!r}, storage={store}, L={self._L})"


def _matching_result_flatten(r: MatchingResult):
    return (r.assigned, r._mb, r._mb_packed), (r._L,)


def _matching_result_unflatten(aux, children):
    assigned, mb, mb_packed = children
    obj = object.__new__(MatchingResult)
    object.__setattr__(obj, "assigned", assigned)
    object.__setattr__(obj, "_mb", mb)
    object.__setattr__(obj, "_mb_packed", mb_packed)
    object.__setattr__(obj, "_L", aux[0])
    return obj


jax.tree_util.register_pytree_node(
    MatchingResult, _matching_result_flatten, _matching_result_unflatten
)


def eligibility(weights: jax.Array, thresholds: jax.Array) -> jax.Array:
    """te[e, i] = w(e) >= (1+eps)^i — the L-bit eligibility vector (§4.4 Stage 4)."""
    return weights[:, None] >= thresholds[None, :]
