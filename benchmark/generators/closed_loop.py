"""Closed-loop traffic: ``inflight`` callers, each sending its next
operation when the last one has returned (``rados bench -t``, YCSB's
client threads).  One generator for every mix; a mix is a data file
under ``benchmark/traffic/`` with these parameters:

- ``object_bytes``, ``objects``: size and number of the objects (records);
- ``inflight``: callers;
- ``mix``: shares of ``read`` and ``write`` (a write replaces the whole
  object, ``write_full``);
- ``order``: ``ring`` (object i, i+1, ... round and round), ``shuffle``
  (each object once in a seeded order, then the same order again) or
  ``zipfian`` (YCSB's scrambled zipfian, constant ``zipf_theta``);
- ``payload``: ``pool`` random blocks made from the seed, and whether a
  payload carries a ``tag`` (object number and version in its first 16
  bytes: small records that many callers rewrite at once);
- ``keep_every``: of the answers to reads, every how-many-th is kept
  for the comparison after the window (1: all).

Every seed gets the same multiset of operations in another order: the
sequence is a seeded permutation of one fixed list.
"""

from __future__ import annotations

import contextlib
import itertools
import struct
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field

import numpy as np

SEQUENCE = 1 << 17
TAG = struct.Struct("<QQ")
VERSION_STRIDE = 29


@dataclass
class Op:
    index: int
    kind: str                      # "read" | "write"
    key: int
    version: int | None            # of a write
    t_submit: float
    t_done: float | None = None    # None: never answered
    error: str | None = None
    answer: bytes | None = None    # kept answers of reads only
    answer_len: int | None = None

    @property
    def ok(self) -> bool:
        """Answered, and without an error."""
        return self.error is None and self.t_done is not None


@dataclass
class Plan:
    object_bytes: int
    objects: int
    inflight: int
    kinds: np.ndarray              # bool per sequence slot: True = write
    keys: np.ndarray               # object number per sequence slot
    pool: list[bytes]
    tag: bool
    keep_every: int
    seed: int
    versions: dict[int, int] = field(default_factory=dict)

    def name(self, key: int) -> str:
        return f"obj{key:07d}"

    def payload(self, key: int, version: int) -> bytes:
        block = self.pool[(key + VERSION_STRIDE * version) % len(self.pool)]
        if self.tag:
            return TAG.pack(key, version) + bytes(block[TAG.size:])
        return block

    def slot(self, index: int) -> tuple[str, int]:
        i = index % len(self.keys)
        return ("write" if self.kinds[i] else "read"), int(self.keys[i])

    def keeps(self, index: int) -> bool:
        return (index * 2654435761 + self.seed) % self.keep_every == 0


def zipf_counts(n_keys: int, theta: float, total: int) -> np.ndarray:
    """How often each popularity rank appears among ``total`` draws of
    a zipfian with constant ``theta``: the expected counts, rounded so
    that they sum to ``total`` (largest remainders first)."""
    p = 1.0 / np.arange(1, n_keys + 1, dtype=np.float64) ** theta
    want = p / p.sum() * total
    counts = np.floor(want).astype(np.int64)
    short = total - int(counts.sum())
    counts[np.argsort(-(want - counts), kind="stable")[:short]] += 1
    return counts


def make_pool(seed: int, blocks: int, size: int, as_bytes: bool) -> list:
    """``blocks`` random payloads of ``size`` bytes, block i from its
    own stream of the seed, made by a few threads (numpy's generators
    release the lock).  A mix that writes gets ``bytes``, so that the
    window copies nothing; a mix that only reads gets views of the
    arrays, which set-up copies once as it writes them."""
    words = -(-size // 8)

    def block(i: int):
        raw = np.random.PCG64([seed, 0x706F, i]).random_raw(words)
        return raw.tobytes()[:size] if as_bytes \
            else memoryview(raw).cast("B")[:size]

    with ThreadPoolExecutor(8, thread_name_prefix="bench-pool") as ex:
        return list(ex.map(block, range(blocks)))


def make_plan(traffic: dict, seed: int) -> Plan:
    rng = np.random.default_rng([seed, 0x6265])
    n = int(traffic["objects"])
    size = int(traffic["object_bytes"])
    share_w = float(traffic["mix"].get("write", 0.0))
    share_r = float(traffic["mix"].get("read", 0.0))
    if abs(share_w + share_r - 1.0) > 1e-9:
        raise ValueError("the mix's shares do not sum to 1")
    order = traffic["order"]
    if order == "ring":
        length = SEQUENCE - SEQUENCE % n
        keys = np.arange(length, dtype=np.int64) % n
    elif order == "shuffle":
        length = n
        keys = rng.permutation(n)
    elif order == "zipfian":
        length = SEQUENCE
        ranks = np.repeat(np.arange(n), zipf_counts(
            n, float(traffic["zipf_theta"]), length))
        keys = rng.permutation(n)[ranks]       # scrambled: rank -> object
        keys = keys[rng.permutation(length)]
    else:
        raise ValueError(f"unknown order {order!r}")
    n_w = int(round(share_w * length))
    kinds = np.zeros(length, bool)
    kinds[:n_w] = True
    if 0 < n_w < length:
        kinds = kinds[rng.permutation(length)]
    pay = traffic["payload"]
    pool = make_pool(seed, int(pay["pool"]), size, as_bytes=n_w > 0)
    return Plan(object_bytes=size, objects=n,
                inflight=int(traffic["inflight"]), kinds=kinds, keys=keys,
                pool=pool, tag=bool(pay["tag"]),
                keep_every=int(traffic.get("keep_every", 1)), seed=seed)


def run(plan: Plan, dep, seconds: float, annotate: bool = False,
        grace: float = 60.0) -> tuple[list[Op], float, float]:
    """Drive ``dep`` (``write(name, bytes)`` / ``read(name)``) for
    ``seconds``; returns the operations submitted in the window, each
    waited for up to ``grace`` seconds past the close, with the
    window's start and end on ``time.perf_counter``."""
    counter = itertools.count()
    vlock = threading.Lock()
    lists: list[list[Op]] = [[] for _ in range(plan.inflight)]
    if annotate:
        from jax.profiler import TraceAnnotation as span
    else:
        span = contextlib.nullcontext
    start = threading.Barrier(plan.inflight + 1)
    deadline = [0.0]

    def caller(mine: list[Op]) -> None:
        start.wait()
        while True:
            index = next(counter)
            kind, key = plan.slot(index)
            version = payload = None
            if kind == "write":
                with vlock:
                    version = plan.versions[key] = \
                        plan.versions.get(key, 0) + 1
                payload = plan.payload(key, version)
            name = plan.name(key)
            now = time.perf_counter()
            if now >= deadline[0]:
                if kind == "write":      # not sent: the version is free
                    with vlock:
                        if plan.versions.get(key) == version:
                            plan.versions[key] = version - 1
                return
            op = Op(index, kind, key, version, now)
            mine.append(op)
            try:
                with span(f"client-{kind}"):
                    got = (dep.write(name, payload) if kind == "write"
                           else dep.read(name))
                op.t_done = time.perf_counter()
                if kind == "read":
                    op.answer_len = len(got)
                    if plan.keeps(index):
                        op.answer = got
            except Exception as e:  # noqa: BLE001 - a failed operation
                op.t_done = time.perf_counter()
                op.error = repr(e)

    threads = [threading.Thread(target=caller, args=(lst,), daemon=True,
                                name=f"bench-caller-{i}")
               for i, lst in enumerate(lists)]
    for t in threads:
        t.start()
    t0 = time.perf_counter()
    deadline[0] = t0 + seconds
    start.wait()
    for t in threads:
        t.join(max(0.0, deadline[0] + grace - time.perf_counter()))
    ops = sorted((op for lst in lists for op in list(lst)),
                 key=lambda o: o.index)
    return ops, t0, deadline[0]
