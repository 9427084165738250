"""StripeCodec — the flagship EC compute pipeline.

The TPU-shaped equivalent of the reference OSD's stripe hot path
(ECUtil.cc:488-514 shard_extent_map_t::encode -> encode_chunks and :639-747
decode): a batch of stripes lives as a (k, batch*chunk) uint8 tensor in HBM
(SURVEY.md §5: a stripe is a (k+m, chunk) tile; batching stripes widens the
column axis), and encode/decode are traced GF(2^8) region matmuls.

This is what __graft_entry__.entry() exposes and what bench.py measures.
"""

from __future__ import annotations

import numpy as np

from ..ops import gf256
from ..ops.ec_kernels import gf_matmul_graph


def coding_matrix(k: int, m: int, technique: str = "reed_sol_van") -> np.ndarray:
    if technique == "reed_sol_van":
        return gf256.vandermonde_matrix(k, m)
    if technique in ("cauchy", "cauchy_orig"):
        return gf256.cauchy_matrix(k, m)
    if technique == "cauchy_good":
        return gf256.cauchy_good_matrix(k, m)
    raise ValueError(f"unknown technique {technique!r}")


class StripeCodec:
    """k+m systematic stripe codec with jit-friendly encode/decode graphs."""

    def __init__(self, k: int = 8, m: int = 3,
                 technique: str = "reed_sol_van"):
        self.k, self.m, self.technique = k, m, technique
        self.matrix = coding_matrix(k, m, technique)
        self.full = np.concatenate(
            [np.eye(k, dtype=np.uint8), self.matrix])

    def encode_graph(self):
        """fn(data (k, N) uint8) -> parity (m, N); pure jnp, jittable
        and shard_map-safe (N % 4 == 0)."""
        return gf_matmul_graph(self.matrix)

    def stack_rows_graph(self, rows: list[int]):
        """fn(data (k, N)) -> the given rows of the full [I; C] stack —
        what a shard-parallel device computes for the chunks it owns."""
        return gf_matmul_graph(self.full[rows])

    def decode_graph(self, available: list[int]):
        """fn(survivors (k, N)) -> data (k, N) for a static erasure
        signature (the decode-matrix inversion happens at trace time, as
        the reference caches inverted tables per signature,
        ErasureCodeIsa.cc:513-563)."""
        D = gf256.decode_matrix(self.matrix, self.k, available)
        return gf_matmul_graph(D)
