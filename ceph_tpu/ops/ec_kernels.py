"""JAX/Pallas GF(2^8) region kernels — the TPU erasure-code hot path.

This is the TPU-native replacement for the SIMD region kernels the
reference gets from jerasure/gf-complete/ISA-L (the hot loop behind
ECUtil.cc:488-514's encode_chunks and the benchmark's encode loop,
ceph_erasure_code_benchmark.cc:186-191).

Formulation
-----------
A GF(2^8) multiply by a *constant* c is GF(2)-linear on the bits of the
operand:  c*b = XOR_s bit_s(b) * (c * x^s).  Working on uint32 lanes that
each hold 4 independent bytes of a chunk:

    y32 ^= ((x32 >> s) & 0x01010101) * byte(c * x^s)      for s in 0..7

— the shifted mask extracts bit s of each byte into its low bit-position,
and the integer multiply broadcasts the constant byte into every byte slot
with no carries (mask bytes are 0/1, products fit a byte).  The whole
(m, k) matrix multiply unrolls at trace time into a static chain of
shift/and/mul/xor VPU ops: no gathers, no tables, no data-dependent control
flow — exactly what XLA/Mosaic want.  Coefficient 0 contributes nothing and
coefficient 1 is a single XOR, so XOR-heavy matrices (Vandermonde row 0,
cauchy_good's all-ones row) cost almost nothing — the same optimisation
jerasure's XOR-schedule (cauchy_good) path performs on CPUs.

The same trace builds three ways: a Pallas TPU kernel (data staged through
VMEM in blocks), the identical jnp graph for CPU/debug, and Pallas
interpret mode for CI coverage of the kernel itself.

Kernel realizations (KERNELS), one per platform, chosen by the platform
(platform_kernel):

- ``pallas`` — the chain above as a Pallas kernel: what a TPU runs (and
  interpret mode, for CI coverage of the kernel body);
- ``xla``    — the same chain as a plain jnp graph: every other
  platform, and the body of a shard_map (a Pallas call is a launch, not
  an embeddable sub-graph).

Decodes run the same chain with the matrix as a runtime operand
(gf_generic_lanes: one program per shape, whatever the survivor set),
and the GF(2) bit-matrix code family runs its CSE'd XOR schedule
(ScheduledXor, ops/xor_schedule.py).

Bytes and lanes
---------------
Bytes live on the host, uint32 lanes live on the device.  A chunk's
byte view IS its lane view (little-endian u32 of 4 consecutive bytes),
so the host converts with a zero-copy numpy ``.view`` on either side of
the copy (bytes_as_lanes / lanes_as_bytes).  No program that reaches an
accelerator holds a uint8<->uint32 ``bitcast_convert_type``: the TPU
compiler tiles the (c, n4, 4) uint8 intermediate with its minor
dimension padded from 4 to 128 and takes over a minute per shape.  The
byte-domain graph builder (gf_matmul_graph) remains for CPU tests and
the StripeCodec graphs only.
"""

from __future__ import annotations

import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np

from . import gf256
from .xor_schedule import XorSchedule, build_schedule

_MASK = 0x01010101  # low bit of each byte lane in a uint32

#: realizations of the region multiply compiled for one matrix
KERNELS = ("xla", "pallas")

#: uint32 lanes per tile row: every lane-domain launch is a whole number
#: of these (512 bytes)
LANE_TILE = 128
#: Pallas block cap, in uint32 lanes per row (32 KiB/row)
BLOCK = 8192
#: scoped VMEM a Pallas kernel may use on the chips this runs on
VMEM_LIMIT = 16 << 20
#: column block of the generic (runtime-matrix) realization, in lanes
GENERIC_BLOCK = 1 << 20


def fit_block(n_rows_live: int, cap: int = BLOCK) -> int:
    """Largest power-of-two block (lanes per row) whose kernel body fits
    the scoped VMEM: a (1, block) uint32 row occupies a whole 8-sublane
    tile row, 32 * block bytes, and the scheduled-XOR bodies keep one
    per schedule node — so the node count sets the block."""
    block = cap
    while block > LANE_TILE and 32 * block * n_rows_live > VMEM_LIMIT:
        block //= 2
    return block


def grid_block(n4: int, cap: int = BLOCK) -> int:
    """Largest whole-tile block <= cap that divides n4, so a Pallas grid
    of n4 // block steps covers the launch with no ragged tail."""
    if n4 % LANE_TILE:
        raise ValueError(
            f"lane launches want n4 % {LANE_TILE} == 0; got {n4}")
    tiles = n4 // LANE_TILE
    best = 1
    for d in range(1, min(tiles, cap // LANE_TILE) + 1):
        if tiles % d == 0:
            best = d
    return best * LANE_TILE


def lane_quantum(L: int, block: int = BLOCK) -> int:
    """Byte quantum a host buffer of L bytes pads to before it is viewed
    as lanes: one tile (512 bytes) up to a block, whole blocks beyond."""
    return 4 * LANE_TILE if L <= 4 * block else 4 * block


def bytes_as_lanes(data: np.ndarray, block: int = BLOCK) -> np.ndarray:
    """Host (c, L) uint8 -> (c, n4) uint32 lanes, zero-padded to the
    lane quantum; a zero-copy view when L is already whole."""
    L = data.shape[-1]
    pad = (-L) % lane_quantum(L, block)
    if pad:
        data = np.pad(data, ((0, 0), (0, pad)))
    return np.ascontiguousarray(data).view(np.uint32)


def lanes_as_bytes(y32: np.ndarray, L: int | None = None) -> np.ndarray:
    """Host (r, n4) uint32 lanes -> (r, L) uint8 (zero-copy view, the
    pad columns trimmed)."""
    out = np.ascontiguousarray(y32).view(np.uint8)
    return out if L is None or L == out.shape[-1] else out[:, :L]


_SHARED_OPS: dict = {}
_SHARED_LOCK = threading.Lock()
SHARED_OPS_CAP = 256


def platform_kernel() -> str:
    """The realization this process's platform runs: the Pallas kernel
    on a TPU, the XLA graph everywhere else."""
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def region_matmul(M: np.ndarray) -> "RegionMatmul":
    """Process-wide RegionMatmul LRU: an in-process cluster holds one
    codec per OSD, and identical matrices must share ONE compiled
    program per shape instead of compiling once per OSD."""
    M = np.ascontiguousarray(M, dtype=np.uint8)
    key = (M.shape, M.tobytes())
    with _SHARED_LOCK:
        op = _SHARED_OPS.pop(key, None)
        if op is None:
            op = RegionMatmul(M)
            if len(_SHARED_OPS) >= SHARED_OPS_CAP:
                _SHARED_OPS.pop(next(iter(_SHARED_OPS)))
        _SHARED_OPS[key] = op
    return op


def _terms(M: np.ndarray) -> tuple[tuple[tuple[int, int, int], ...], ...]:
    """Static per-output-row term lists: row i -> ((j, s, v), ...) with
    v = M[i,j] * x^s != 0; a (j, -1, 0) entry marks a plain XOR (coef 1)."""
    M = np.asarray(M, dtype=np.uint8)
    rows = []
    for i in range(M.shape[0]):
        row: list[tuple[int, int, int]] = []
        for j in range(M.shape[1]):
            c = int(M[i, j])
            if c == 0:
                continue
            if c == 1:
                row.append((j, -1, 0))
                continue
            for s in range(8):
                v = int(gf256.gf_mul(c, 1 << s))
                if v:
                    row.append((j, s, v))
        rows.append(tuple(row))
    return tuple(rows)


def _accumulate_row(x, terms):
    """XOR-accumulate one output row from input rows x (c, n) uint32."""
    acc = None
    for j, s, v in terms:
        xj = x[j : j + 1, :]
        t = xj if s < 0 else (
            (xj >> jnp.uint32(s)) & jnp.uint32(_MASK)) * jnp.uint32(v)
        acc = t if acc is None else acc ^ t
    if acc is None:
        return jnp.zeros_like(x[0:1, :])
    return acc


def _rows_op(x, terms_all):
    return jnp.concatenate([_accumulate_row(x, t) for t in terms_all], axis=0)


def _named(fn, name: str):
    """``fn`` under ``name``: what ``jax.jit`` calls the program it
    compiles (``jit_<name>`` on a trace's ``XLA Modules`` line, the
    ``%<name>`` prefix of its device operations), so a trace tells the
    encode from the runtime-matrix decode by name and not by shape.
    A name is ``ec_encode_<kernel>_<r>x<c>`` for a program compiled for
    one matrix (every served encode; a per-op decode with a cached
    matrix is the same kind of program), ``ec_bitmatrix_<R>x<C>`` for a
    GF(2) schedule, ``ec_decode_rt`` for the program that takes its
    matrix as data; ``_fold<n>`` where the fold of n device buffers is
    part of the program."""
    fn.__name__ = fn.__qualname__ = name
    return fn


def _pallas_region_kernel(rows_op):
    """Pallas kernel body around any (c, n) -> (r, n) uint32 rows op —
    shared by the bit-term chain and the scheduled-XOR realization."""
    def kernel(x_ref, o_ref):
        o_ref[...] = rows_op(x_ref[...])

    return kernel


# ---------------------------------------------------------------------------
# GF(2) XOR schedules (the bit-matrix code family's executor)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=128)
def _cached_schedule(key: bytes, shape: tuple[int, int]) -> XorSchedule:
    B = np.frombuffer(key, dtype=np.uint8).reshape(shape)
    return build_schedule(B)


def _eval_schedule_nodes(sched: XorSchedule, nodes: list) -> list:
    """Run the intermediate op chain in place (inputs pre-filled)."""
    for dst, a, b in sched.ops:
        nodes[dst] = nodes[a] ^ nodes[b]
    return nodes


def _combine_terms(nodes: list, terms: tuple[int, ...]):
    acc = None
    for t in terms:
        acc = nodes[t] if acc is None else acc ^ nodes[t]
    return acc


def _column_blocks(tile, x32, r: int, block: int):
    """Apply ``tile`` ((c, n) -> (r, n) lanes) over column blocks of at
    most ``block`` lanes in a fori_loop that updates the output in
    place, so a realization whose temporaries are a multiple of its
    input (bit-planes) stays bounded whatever the fold's width."""
    n4 = x32.shape[-1]
    if n4 <= block or n4 % LANE_TILE:
        return tile(x32)
    blk = grid_block(n4, block)

    def body(i, out):
        xs = jax.lax.dynamic_slice_in_dim(x32, i * blk, blk, axis=1)
        return jax.lax.dynamic_update_slice_in_dim(
            out, tile(xs), i * blk, axis=1)

    return jax.lax.fori_loop(0, n4 // blk, body,
                             jnp.zeros((r, n4), dtype=jnp.uint32))


def gf_lanes_graph(M: np.ndarray):
    """Lane-domain graph fn(x32 (c, n4) u32) -> (r, n4) u32: the xla
    realization as what shard_map bodies embed, so a sharded launch is
    lanes in and lanes out."""
    M = np.asarray(M, dtype=np.uint8)
    terms_all = _terms(M)

    def xla_rows(x32):
        return _rows_op(x32, terms_all)

    return _named(xla_rows, f"ec_encode_xla_{M.shape[0]}x{M.shape[1]}")


# ---------------------------------------------------------------------------
# generic: the matrix is a runtime operand
# ---------------------------------------------------------------------------

def coef_table(M: np.ndarray) -> np.ndarray:
    """(r, c, 8) uint32 table V[i, j, s] = M[i, j] * x^s over GF(2^8):
    the bit-term constants of _terms as DATA, so one compiled program
    serves every (r, c) matrix."""
    M = np.asarray(M, dtype=np.uint8)
    return gf256.gf_mul(M[:, :, None],
                        (1 << np.arange(8, dtype=np.uint8))[None, None, :]
                        ).astype(np.uint32)


def gf_generic_lanes(v, x32):
    """out(r, n4) = M @ x32 over GF(2^8) with M given as its coef_table
    ``v`` (r, c, 8): the same shift/mask/multiply/xor bit-term chain as
    the static kernels, none of it specialised to the matrix.  Decode
    matrices are as many as erasure signatures (any k survivors of
    k + m), and a program compiled per matrix would compile in the IO
    path on every new one; this one compiles once per shape."""
    sh = jnp.arange(8, dtype=jnp.uint32)
    v = v.astype(jnp.uint32)[..., None]

    def tile(xs):
        planes = (xs[:, None, :] >> sh[None, :, None]) & jnp.uint32(_MASK)
        return jax.lax.reduce(planes[None] * v, jnp.uint32(0),
                              jax.lax.bitwise_xor, (1, 2))

    return _column_blocks(tile, x32, v.shape[0], GENERIC_BLOCK)


def ec_decode_rt(v, x32):
    """gf_generic_lanes under the name its compiled programs carry."""
    return gf_generic_lanes(v, x32)


def ec_decode_rt_fold(v, *parts):
    """The fold of per-op device buffers and the runtime-matrix
    multiply as ONE program."""
    return gf_generic_lanes(v, jnp.concatenate(parts, axis=1))


generic_lanes = jax.jit(ec_decode_rt)
generic_parts = jax.jit(ec_decode_rt_fold)


def _sched_plane_rows(x32, sched: XorSchedule):
    """(n_in, n4) uint32 plane rows -> (n_out, n4): the schedule applied
    to rows that ARE the planes already (the bit-matrix code family's
    packet rows) — no bit extraction, no packing."""
    nodes: list = [None] * (sched.n_in + len(sched.ops))
    for p in sched.used_inputs:
        nodes[p] = x32[p: p + 1, :]
    _eval_schedule_nodes(sched, nodes)
    rows = []
    for terms in sched.outputs:
        acc = _combine_terms(nodes, terms)
        rows.append(acc if acc is not None
                    else jnp.zeros_like(x32[0:1, :]))
    return jnp.concatenate(rows, axis=0)


def _pallas_lanes(rows_op, r: int, c: int, n4: int, block: int,
                  interpret: bool, name: str):
    """(c, n4) -> (r, n4) uint32 lanes as a Pallas grid over VMEM blocks
    of ``block`` lanes per row (block divides n4); ``name`` is the
    kernel's in a trace."""
    from jax.experimental import pallas as pl

    kernel = _pallas_region_kernel(rows_op)

    def col_block(g):
        return (0, g)

    def run(x32):
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((r, n4), jnp.uint32),
            grid=(n4 // block,),
            in_specs=[pl.BlockSpec((c, block), col_block)],
            out_specs=pl.BlockSpec((r, block), col_block),
            interpret=interpret,
            name=name,
        )(x32)

    return run


class _LaneOp:
    """Shared launch plumbing of the lane-domain executors: a per-shape
    jit LRU, the Pallas-or-graph choice, and the host entry that views
    bytes as lanes before the copy in and lanes as bytes after the copy
    out."""

    #: rows in / rows out, set by subclasses
    r: int
    c: int
    #: what the compiled programs are called (``_named``)
    label: str = "ec_lanes"

    def _init_launch(self, interpret: bool, pallas: bool,
                     block: int) -> None:
        on_tpu = jax.default_backend() == "tpu"
        self._interpret = interpret and not on_tpu
        self._use_pallas = pallas and (on_tpu or self._interpret)
        self.block = block
        self._shape_cache: dict = {}
        # one op serves many threads (OSD shard workers, batcher
        # flushers); the LRU touch and eviction must not interleave
        self._cache_lock = threading.Lock()

    def _rows_core(self):
        raise NotImplementedError

    def _lanes_op(self, n4: int):
        """The (c, n4) -> (r, n4) uint32 lane computation: a Pallas grid
        over VMEM blocks on TPU (or interpret mode), the identical jnp
        graph elsewhere."""
        core = self._rows_core()
        if not self._use_pallas:
            return core
        return _pallas_lanes(core, self.r, self.c, n4,
                             grid_block(n4, self.block), self._interpret,
                             self.label)

    def _build(self, key: tuple):
        if key[0] == "fold":
            return self._build_fold(key[1], key[2])
        run = self._lanes_op(key[1])

        def lanes(x32):
            return run(x32)

        return jax.jit(_named(lanes, self.label))

    def _build_fold(self, n_parts: int, w4: int):
        run = self._lanes_op(n_parts * w4)

        def fold(*parts):
            return run(jnp.concatenate(parts, axis=1))

        return jax.jit(_named(fold, f"{self.label}_fold{n_parts}"))

    def _compiled(self, key: tuple):
        # true LRU: a hot shape must not be evicted just because it was
        # compiled first (a hit re-inserts behind newer one-shots).
        # Building under the lock is fine — jax.jit wrapping is lazy;
        # the expensive trace happens at first call, outside the lock.
        with self._cache_lock:
            fn = self._shape_cache.pop(key, None)
            if fn is None:
                fn = self._build(key)
                if len(self._shape_cache) >= 16:
                    self._shape_cache.pop(next(iter(self._shape_cache)))
            self._shape_cache[key] = fn
        return fn

    def lanes_fn(self, n4: int):
        """The jitted lane program for width n4 (what encode_lanes
        launches) — exposed so callers can lower/compile it for a
        described device."""
        if n4 % LANE_TILE:
            raise ValueError(
                f"lane launches want n4 % {LANE_TILE} == 0; got {n4}")
        return self._compiled(("u32", n4))

    def encode_lanes(self, x32) -> jax.Array:
        """Raw lane-domain entry: x32 (c, n4) uint32 -> (r, n4) uint32
        device array, no host sync.  n4 must be a whole number of
        128-lane tiles; host callers get there with bytes_as_lanes —
        zero-copy — rather than paying a device-side bitcast."""
        if x32.ndim != 2 or x32.shape[0] != self.c:
            raise ValueError(
                f"expected ({self.c}, n4) lanes, got {x32.shape}")
        return self.lanes_fn(x32.shape[-1])(x32)

    def folded_fn(self, n_parts: int, w4: int):
        """The jitted fold + launch for ``n_parts`` device buffers of
        (c, w4) lanes each: concatenation and kernel are ONE program, so
        a flush's fold is never a separate eagerly compiled concat."""
        if (n_parts * w4) % LANE_TILE:
            raise ValueError(
                f"folded launches want a whole number of {LANE_TILE}-"
                f"lane tiles; got {n_parts} x {w4}")
        return self._compiled(("fold", n_parts, w4))

    def encode_parts(self, parts) -> jax.Array:
        """Folded launch over per-op device buffers ((c, w4) uint32
        lanes each, one width) -> (r, len(parts) * w4) device lanes."""
        return self.folded_fn(len(parts), parts[0].shape[-1])(*parts)

    def __call__(self, data) -> np.ndarray:
        """Host bytes in, host bytes out: (c, L) uint8 -> (r, L) uint8.
        Bytes are viewed as lanes before the copy in and as bytes after
        the ONE copy out; no device program sees a byte.  Callers that
        keep results on the device hold lanes and use encode_lanes."""
        if isinstance(data, jax.Array):
            raise TypeError(
                "device arrays are uint32 lanes: view host bytes with "
                "bytes_as_lanes and launch encode_lanes")
        data = np.ascontiguousarray(data, dtype=np.uint8)
        if data.ndim != 2 or data.shape[0] != self.c:
            raise ValueError(
                f"expected ({self.c}, L) data, got {data.shape}")
        L = data.shape[1]
        if L == 0:
            return np.zeros((self.r, 0), dtype=np.uint8)
        y32 = self.encode_lanes(bytes_as_lanes(data, self.block))
        return lanes_as_bytes(np.asarray(y32), L)


class ScheduledXor(_LaneOp):
    """out(R, L) = B(R, C) @ rows(C, L) over GF(2), executed as the
    CSE'd XOR schedule on uint32 lanes — the shared bitxor executor for
    the GF(2) bit-matrix code family (ec/bitmatrix_code.py routes its
    jerasure-parity packet rows here on the jax backend) and any caller
    already holding plane rows.  Pallas on TPU (or interpret for CI),
    the identical jnp graph elsewhere; same 512-byte lane quantum and
    per-shape jit LRU as RegionMatmul."""

    def __init__(self, B: np.ndarray, *, interpret: bool = False):
        self.B = np.ascontiguousarray(B, dtype=np.uint8) & 1
        self.R, self.C = self.B.shape
        self.r, self.c = self.R, self.C
        self.label = f"ec_bitmatrix_{self.R}x{self.C}"
        self.sched = _cached_schedule(self.B.tobytes(), self.B.shape)
        # every schedule node is a live (1, block) row in the body
        self._init_launch(interpret, True, fit_block(
            self.sched.n_in + len(self.sched.ops) + self.R))

    def _rows_core(self):
        sched = self.sched

        def plane_rows(x32):
            return _sched_plane_rows(x32, sched)

        return plane_rows


def gf_matmul_graph(M: np.ndarray):
    """Return a pure, jit-friendly fn(data (c, L) uint8) -> (r, L) uint8
    computing M @ data over GF(2^8) as a plain jnp graph (no pallas_call),
    for embedding inside larger jitted/shard_mapped programs (L % 4 == 0)."""
    terms_all = _terms(M)
    r, c = np.asarray(M).shape

    def fn(data_u8):
        if data_u8.shape[0] != c:
            raise ValueError(f"expected {c} rows, got {data_u8.shape[0]}")
        n4 = data_u8.shape[-1] // 4
        x32 = jax.lax.bitcast_convert_type(
            data_u8.reshape(c, n4, 4), jnp.uint32)
        y32 = _rows_op(x32, terms_all)
        return jax.lax.bitcast_convert_type(y32, jnp.uint8).reshape(r, n4 * 4)

    return fn


class RegionMatmul(_LaneOp):
    """out(r, L) = M(r, c) @ data(c, L) over GF(2^8), JAX-compiled.

    Stripes batch by widening L (columns are independent), which is how
    the stripe batcher feeds many stripes per launch (SURVEY.md §5
    long-context analogue: a stripe batch is a (c, batch*chunk) tensor).
    The device side is uint32 lanes in, lanes out (encode_lanes); the
    host entry (__call__ on numpy bytes) views on either side of the
    copies.
    """

    def __init__(self, M: np.ndarray, *, interpret: bool = False,
                 kernel: str = "auto"):
        """``kernel`` names the realization (KERNELS); ``auto`` is the
        platform's (platform_kernel).  ``interpret=True`` runs the
        Pallas kernel in interpret mode off-TPU (CI coverage of the
        kernel body), and is what ``auto`` means there.  ``pallas``
        with neither a TPU nor interpret raises ValueError."""
        self.M = np.ascontiguousarray(M, dtype=np.uint8)
        self.r, self.c = self.M.shape
        if kernel == "auto":
            kernel = "pallas" if interpret else platform_kernel()
        if kernel not in KERNELS:
            raise ValueError(f"unknown kernel {kernel!r}")
        if kernel == "pallas" and not (
                interpret or jax.default_backend() == "tpu"):
            raise ValueError(
                "pallas kernel needs the TPU backend or interpret=True")
        self.kernel = kernel
        self.label = f"ec_encode_{kernel}_{self.r}x{self.c}"
        self._terms = _terms(self.M)
        self._init_launch(interpret, kernel == "pallas", BLOCK)

    def _rows_core(self):
        """The raw (c, n) -> (r, n) uint32 lanes computation — what the
        Pallas kernel body, the jnp graph, and interpret mode share."""
        terms_all = self._terms

        def bit_term_rows(x32):
            return _rows_op(x32, terms_all)

        return bit_term_rows
