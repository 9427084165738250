"""Distributed stripe EC over a device mesh — the ICI-native "cluster".

The TPU mapping of the reference's distributed write path (SURVEY.md §3.1
EC variant: ECBackend.submit_transaction -> per-shard MOSDECSubOpWrite
fan-out over the cluster messenger) re-designed for SPMD over a
("dp", "shard") mesh:

- encode runs **column-sharded** ("sp": each device holds a slice of every
  chunk's columns — the striping/sequence-parallel analogue, SURVEY.md §5),
  so parity is computed with zero communication;
- chunk *placement* is one `all_to_all` that re-lays the stripe from
  column-sharded to row-sharded ownership (each shard device ends up
  owning whole chunks — the acting-set fan-out, but as a single ICI
  collective instead of k+m messenger sends);
- rebalance/backfill movement is a `ppermute` of chunk rows around the
  shard ring (the chunk_mapping/pg-remap analogue, ECUtil.h:477-517);
- degraded reads `all_gather` the surviving rows and decode locally (the
  ReadPipeline fan-in, ECCommon.h:352-420);
- cluster-wide stats (bytes/digest) reduce with `psum` (the PGStats ->
  mgr report analogue).
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

try:  # jax >= 0.8
    from jax import shard_map
except ImportError:  # pragma: no cover - older jax
    from jax.experimental.shard_map import shard_map

from ..models.stripe_codec import StripeCodec


def stage_folded(rows: np.ndarray, mesh: Mesh, axis: str = "shard"):
    """Stage a host fold DIRECTLY into its mesh sharding: ``device_put``
    with the folded launch's NamedSharding moves one column slice per
    device, instead of landing the whole tensor on device 0 and paying
    an on-mesh reshard when the jitted shard_map consumes it — the
    sharded half of the device-resident stripe plane's single-h2d
    contract.  The copy is metered on the ``ec_stage_h2d_*`` staging
    counters (one copy event: the slices leave the host together).
    Device-resident inputs pass through untouched — the jit reshards
    them on-device."""
    if not isinstance(rows, np.ndarray):
        return rows
    import time

    from jax.sharding import NamedSharding

    from ..utils import staging
    t0 = time.perf_counter()
    dev = jax.device_put(rows, NamedSharding(mesh, P(None, axis)))
    # latency only on the synchronous CPU backend — an async device_put
    # returns at dispatch and would book dispatch time as the copy
    staging.note_h2d(rows.nbytes,
                     time.perf_counter() - t0
                     if staging.backend_is_cpu() else None)
    return dev


def make_folded_matmul(M: np.ndarray, mesh: Mesh, axis: str = "shard"):
    """Mesh-sharded folded region multiply: fn(rows (c, n4) uint32
    lanes) -> (r, n4) uint32 lanes computing M @ rows over GF(2^8) with
    the LENGTH axis sharded over `axis` (bytes are viewed as lanes on
    the host, ops/ec_kernels.bytes_as_lanes) — the multi-chip fan-out for the ECBatcher's
    folded (k, sum L) launches (and any other caller already holding
    many stripes as one wide tensor).

    Columns of a region matmul are independent, so the shard_map body
    is the plain encode/decode graph and NO collective runs: an n-device
    mesh encodes an n-writer burst in ~one chip-time.  Callers pad n4
    to a multiple of n_devices; zero columns encode to zero under a
    linear code, so padding slices away exact.  The body embeds the
    xla graph (ops/ec_kernels.gf_lanes_graph).
    """
    from ..ops.ec_kernels import gf_lanes_graph
    g = gf_lanes_graph(np.ascontiguousarray(M, dtype=np.uint8))
    return shard_map(g, mesh=mesh, in_specs=P(None, axis),
                     out_specs=P(None, axis))


def make_folded_generic(mesh: Mesh, axis: str = "shard"):
    """Mesh-sharded folded region multiply whose MATRIX is a runtime
    operand: fn(v (r, c, 8) coef_table, rows (c, n4) lanes) -> (r, n4)
    lanes, the table replicated and the length axis sharded — one
    program per shape for every decode signature
    (ops/ec_kernels.gf_generic_lanes)."""
    from ..ops.ec_kernels import ec_decode_rt
    return shard_map(ec_decode_rt, mesh=mesh,
                     in_specs=(P(), P(None, axis)),
                     out_specs=P(None, axis))


class DistributedStripeEC:
    """Distributed EC pipeline for a StripeCodec over a ("dp","shard") mesh.

    Data model: a batch of stripes (B, k, L) uint8.  B is sharded over
    "dp"; L over "shard" during compute; after placement each shard device
    owns S/n_shard whole chunk rows, where S pads k+m up to a multiple of
    the shard axis (spare rows are zero — "spare OSD" slots).
    """

    def __init__(self, codec: StripeCodec, mesh: Mesh,
                 batch_axes: Sequence[str] = ("dp",)):
        self.codec = codec
        self.mesh = mesh
        self.n_shard = mesh.shape["shard"]
        # the batch dimension may shard over several mesh axes — on a
        # multi-host mesh it is ("host", "dp"): the slow DCN hop only
        # ever carries batch-parallel work, while the chatty "shard"
        # collectives (all_to_all / ppermute) stay inside one host's
        # ICI domain (the scaling-book layout rule; SURVEY.md §2.3
        # TPU-equivalent row — DCN via jax.distributed)
        self.batch_axes = tuple(batch_axes)
        self.n_dp = 1
        for a in self.batch_axes:
            self.n_dp *= mesh.shape[a]
        km = codec.k + codec.m
        self.S = -(-km // self.n_shard) * self.n_shard
        self.spare_rows = self.S - km

    # ---------------- write ----------------
    def make_write_step(self):
        """jit-able fn(data (B, k, L)) -> (stack (B, S, L), digest scalar).

        Output sharding: stack rows over "shard" (chunk ownership), batch
        over "dp"; digest is a psum-reduced uint32 scrub digest.
        """
        k, m, S = self.codec.k, self.codec.m, self.S
        enc = self.codec.encode_graph()

        def local(d):  # (b, k, Lloc) on one device
            b, _, Ll = d.shape
            folded = d.transpose(1, 0, 2).reshape(k, b * Ll)
            par = enc(folded).reshape(m, b, Ll).transpose(1, 0, 2)
            zeros = jnp.zeros((b, S - k - m, Ll), jnp.uint8)
            stack = jnp.concatenate([d, par, zeros], axis=1)  # (b, S, Ll)
            # placement: column-sharded -> row-sharded chunk ownership
            stack = jax.lax.all_to_all(stack, "shard", split_axis=1,
                                       concat_axis=2, tiled=True)
            # scrub digest: cluster-wide reduction of encoded bytes
            digest = jax.lax.psum(
                jnp.sum(par.astype(jnp.uint32)),
                (*self.batch_axes, "shard"))
            return stack, digest

        B = self.batch_axes
        return shard_map(
            local, mesh=self.mesh,
            in_specs=P(B, None, "shard"),
            out_specs=(P(B, "shard", None), P()),
        )

    # ---------------- rebalance / backfill ----------------
    def make_rebalance_step(self, rotate: int = 1):
        """jit-able fn(stack (B, S, L)) -> stack with chunk-row ownership
        rotated `rotate` positions around the shard ring (ppermute) — the
        movement primitive behind pg-remap/backfill."""
        n = self.n_shard

        def local(stack_local):
            perm = [(i, (i + rotate) % n) for i in range(n)]
            return jax.lax.ppermute(stack_local, "shard", perm)

        B = self.batch_axes
        return shard_map(
            local, mesh=self.mesh,
            in_specs=P(B, "shard", None),
            out_specs=P(B, "shard", None),
        )

    # ---------------- degraded read / recovery ----------------
    def make_recovery_step(self, available: Sequence[int]):
        """jit-able fn(stack (B, S, L)) -> data (B, k, L) decoding from the
        static erasure signature `available` (>= k surviving chunk ids).

        all_gathers surviving rows over the shard axis (the fan-in read),
        decodes locally with the inverted matrix, returns column-sharded
        data (ready for re-encode or client return).
        """
        k = self.codec.k
        use = list(available)[:k]
        dec = self.codec.decode_graph(use)

        def local(stack_local):  # (b, S/n, L) — whole rows owned locally
            b = stack_local.shape[0]
            # inverse of the write placement: row-sharded -> column-sharded
            # (each device sends every peer only the column slice it will
            # decode — less ICI traffic than a full-row all_gather, and no
            # decode work is discarded)
            full = jax.lax.all_to_all(stack_local, "shard", split_axis=2,
                                      concat_axis=1, tiled=True)  # (b,S,L/n)
            Ll = full.shape[2]
            surv = full[:, jnp.asarray(use), :]  # (b, k, L/n) static gather
            folded = surv.transpose(1, 0, 2).reshape(k, b * Ll)
            return dec(folded).reshape(k, b, Ll).transpose(1, 0, 2)

        B = self.batch_axes
        return shard_map(
            local, mesh=self.mesh,
            in_specs=P(B, "shard", None),
            out_specs=P(B, None, "shard"),
        )

    # ---------------- partial write: parity delta ----------------
    def make_delta_step(self):
        """jit-able fn(stack (B,S,L) row-sharded, delta (B,k,L)
        column-sharded) -> updated stack.

        The parity-delta partial write (ECUtil encode_parity_delta,
        ECUtil.cc:519-566): GF(2^8) addition is XOR, so
        parity' = parity ^ encode(delta) and data' = data ^ delta —
        the stripe updates without re-reading any other row.  The
        delta encodes column-sharded (zero communication), then one
        all_to_all re-lays it to chunk ownership and the XOR folds in
        locally — same collective budget as a full write, a fraction
        of the FLOPs."""
        k, m, S = self.codec.k, self.codec.m, self.S
        enc = self.codec.encode_graph()

        def local(stack_local, d):  # d: (b, k, Lloc) column-sharded
            b, _, Ll = d.shape
            folded = d.transpose(1, 0, 2).reshape(k, b * Ll)
            par = enc(folded).reshape(m, b, Ll).transpose(1, 0, 2)
            zeros = jnp.zeros((b, S - k - m, Ll), jnp.uint8)
            upd = jnp.concatenate([d, par, zeros], axis=1)
            upd = jax.lax.all_to_all(upd, "shard", split_axis=1,
                                     concat_axis=2, tiled=True)
            return jnp.bitwise_xor(stack_local, upd)

        B = self.batch_axes
        return shard_map(
            local, mesh=self.mesh,
            in_specs=(P(B, "shard", None), P(B, None, "shard")),
            out_specs=P(B, "shard", None),
        )

    # ---------------- per-shard stats: dp-axis reduction ----------------
    def make_stats_step(self):
        """jit-able fn(stack (B,S,L)) -> (S,) uint32 per-chunk-row byte
        totals, reduced over the BATCH axes only (each shard position
        aggregates its own rows across every batch — the per-OSD stats
        report, MPGStats -> mgr aggregation).  On a multi-host mesh this
        is the reduction that rides DCN."""
        def local(stack_local):
            tot = jnp.sum(stack_local.astype(jnp.uint32), axis=(0, 2))
            return jax.lax.psum(tot, self.batch_axes)

        B = self.batch_axes
        return shard_map(
            local, mesh=self.mesh,
            in_specs=P(B, "shard", None),
            out_specs=P("shard"),
        )

    # ---------------- convenience: jitted end-to-end step ----------------
    @functools.cached_property
    def write_step(self):
        return jax.jit(self.make_write_step())

    def recovery_step(self, available: Sequence[int]):
        """Jitted recovery step, cached per erasure signature (the decode
        table cache of the reference, ErasureCodeIsa.cc:513-563)."""
        key = tuple(available)
        cache = self.__dict__.setdefault("_recovery_cache", {})
        fn = cache.get(key)
        if fn is None:
            fn = jax.jit(self.make_recovery_step(available))
            if len(cache) > 128:
                cache.pop(next(iter(cache)))
            cache[key] = fn
        return fn
