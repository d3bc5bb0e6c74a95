"""Substream-centric maximum weighted matching — the paper's contribution.

Public API:
  EdgeStream, SubstreamConfig, MatchingResult  — data types
  mwm_scan              — faithful Listing 1 Part 1 (CS-SEQ oracle)
  substream_matchings   — full [m, L] per-substream membership
  mwm_blocked           — Listing 2 blocked/lexicographic (SC-OPT path)
  mwm_rounds(_sharded)  — deterministic parallel rounds (beyond-paper)
  merge_host/merge_device — Part 2 greedy merge
  gseq                  — Ghaffari (2+eps) baseline (G-SEQ)
  exact_mwm_weight      — networkx oracle (tests/benchmarks)
  mwm_pipeline          — end-to-end: Part 1 + Part 2 → matching + weight
  validate_stream / check_matching — input guard + result invariants
                          (strict / sanitize / off policies, repro.core.guard)
  MatchState            — resumable per-stream-position state (repro.core.state)
  ExecutionGuard        — deadline/retry/straggler guard (repro.core.executor)
"""
from __future__ import annotations

import numpy as np

from repro import obs
from repro.core.bitpack import pack_bits, packed_width, unpack_bits
from repro.core.types import (
    EdgeStream,
    MatchingResult,
    SubstreamConfig,
    eligibility,
)
from repro.core.guard import (
    MatchingInvariantError,
    StreamValidationError,
    ValidationReport,
    check_matching,
    matching_problems,
    stream_problems,
    validate_stream,
)
from repro.core.executor import (
    DeadlineExceededError,
    ExecutionGuard,
    RetriesExhaustedError,
    is_transient,
)
from repro.core.matching import mwm_scan, mwm_waves, substream_matchings
from repro.core.state import MatchState, fingerprint_for
from repro.core.blocked import mwm_blocked, lexicographic_order, permute_stream
from repro.core.rounds import mwm_rounds, mwm_rounds_sharded
from repro.core.merge import merge_host, merge_device, matching_weight
from repro.core.gseq import gseq
from repro.core.exact import exact_mwm_weight


def mwm_pipeline(
    stream: EdgeStream,
    cfg: SubstreamConfig,
    part1: str = "scan",
    K: int = 32,
    **kw,
):
    """End-to-end (4+eps)-approx MWM. Returns (edge_indices, weight).

    part1 in {'scan', 'waves', 'blocked', 'pallas', 'rounds'}. A
    ``telemetry`` session in ``kw`` reaches Part 1 (``waves`` /
    ``pallas``) and the merge.
    """
    if part1 == "scan":
        res = mwm_scan(stream, cfg)
    elif part1 == "waves":
        res = mwm_waves(stream, cfg, **kw)
    elif part1 == "blocked":
        res = mwm_blocked(stream, cfg, K=K, backend="scan")
    elif part1 == "pallas":
        res = mwm_blocked(stream, cfg, K=K, backend="pallas", **kw)
    elif part1 == "rounds":
        res = mwm_rounds(stream, cfg)
    else:
        raise ValueError(part1)
    telemetry = kw.get("telemetry", obs.DISABLED)
    idx = merge_host(stream, res, cfg, telemetry=telemetry)
    return idx, matching_weight(stream, idx, telemetry=telemetry)


__all__ = [
    "EdgeStream",
    "MatchingResult",
    "SubstreamConfig",
    "eligibility",
    "pack_bits",
    "packed_width",
    "unpack_bits",
    "validate_stream",
    "stream_problems",
    "check_matching",
    "matching_problems",
    "StreamValidationError",
    "MatchingInvariantError",
    "ValidationReport",
    "mwm_scan",
    "mwm_waves",
    "substream_matchings",
    "mwm_blocked",
    "lexicographic_order",
    "permute_stream",
    "mwm_rounds",
    "mwm_rounds_sharded",
    "merge_host",
    "merge_device",
    "matching_weight",
    "gseq",
    "exact_mwm_weight",
    "mwm_pipeline",
    "MatchState",
    "fingerprint_for",
    "ExecutionGuard",
    "DeadlineExceededError",
    "RetriesExhaustedError",
    "is_transient",
]
