"""``rbd bench --io-type write --io-pattern rand``: ``inflight`` callers
in a closed loop, each overwriting ``io_bytes`` at an offset aligned to
``io_bytes`` of a block image that lies on RADOS objects of
``object_bytes`` (``image_bytes`` in all), in the shape librbd sends a
write: a RADOS ``write`` of the object that holds the block, at the
block's offset in it.  A mix is a data file under ``benchmark/traffic/``
with those parameters and

- ``io_type`` ``write`` and ``io_pattern`` ``rand`` (nothing else yet);
- ``payload``: ``pool`` random objects and ``patches`` random blocks,
  all made from the seed;
- ``setup``: ``populate`` writes the image whole, so that every timed
  write overwrites live bytes.

The offsets are a seeded permutation of the image's blocks (``rbd
bench`` draws with replacement): every seed sends the same multiset of
blocks in another order, no block twice in a pass, so the image after
the window does not depend on the order of two writes in flight.

The plain model of what an object holds (``Plan.payload``): version 0
is what set-up wrote; version v is that with the object's first v
overwrites, in the order they were sent, laid over it.  The comparison
after the window (``verify.py``) holds the read-back of an object to
its last version and every stored shard, parity included, to
``reference.encode`` of it.  ``Plan.object_bytes`` is the operation's
size, which is what the window's user bytes count.
"""

from __future__ import annotations

import contextlib
import threading
import time
from dataclasses import dataclass, field

import numpy as np

from .closed_loop import Op, make_pool


@dataclass
class Plan:
    object_bytes: int              # of an operation
    stored_bytes: int              # of a RADOS object
    objects: int
    inflight: int
    blocks: np.ndarray             # the image's blocks in the order sent
    pool: list[bytes]
    patches: list[bytes]
    seed: int
    tag: bool = False
    #: object -> its blocks overwritten so far, in the order sent
    sent: dict[int, list[int]] = field(default_factory=dict)

    @property
    def per_object(self) -> int:
        return self.stored_bytes // self.object_bytes

    def name(self, key: int) -> str:
        return f"obj{key:07d}"

    def patch(self, block: int) -> bytes:
        return self.patches[block % len(self.patches)]

    def payload(self, key: int, version: int) -> bytes:
        base = self.pool[key % len(self.pool)]
        if version == 0:
            return base
        buf = bytearray(base)
        for block in self.sent[key][:version]:
            off = block % self.per_object * self.object_bytes
            buf[off:off + self.object_bytes] = self.patch(block)
        return bytes(buf)


def make_plan(traffic: dict, seed: int) -> Plan:
    if (traffic["io_type"], traffic["io_pattern"]) != ("write", "rand"):
        raise ValueError("rbd_bench drives random writes only")
    io = int(traffic["io_bytes"])
    stored = int(traffic["object_bytes"])
    image = int(traffic["image_bytes"])
    if stored % io or image % stored:
        raise ValueError("io_bytes has to divide object_bytes, and "
                         "object_bytes image_bytes")
    pay = traffic["payload"]
    rng = np.random.default_rng([seed, 0x7262])
    raw = np.random.Generator(np.random.PCG64([seed, 0x7062])).bytes(
        int(pay["patches"]) * io)
    return Plan(object_bytes=io, stored_bytes=stored,
                objects=image // stored,
                inflight=int(traffic["inflight"]),
                blocks=rng.permutation(image // io),
                pool=make_pool(seed, int(pay["pool"]), stored,
                               as_bytes=True),
                patches=[raw[i:i + io] for i in range(0, len(raw), io)],
                seed=seed)


def run(plan: Plan, dep, seconds: float, annotate: bool = False,
        grace: float = 60.0) -> tuple[list[Op], float, float]:
    """Drive ``dep`` (its client's RADOS ``write`` at an offset) for
    ``seconds``; returns the operations sent in the window, each waited
    for up to ``grace`` seconds past the close, with the window's start
    and end on ``time.perf_counter``."""
    lock = threading.Lock()
    lists: list[list[Op]] = [[] for _ in range(plan.inflight)]
    if annotate:
        from jax.profiler import TraceAnnotation as span
    else:
        span = contextlib.nullcontext
    start = threading.Barrier(plan.inflight + 1)
    deadline = [0.0]
    drawn = [0]

    def caller(mine: list[Op]) -> None:
        start.wait()
        while True:
            # the draw, the clock and the object's version under one
            # lock: an object's versions are in the order of its sends
            with lock:
                now = time.perf_counter()
                if now >= deadline[0]:
                    return
                index = drawn[0]
                drawn[0] += 1
                block = int(plan.blocks[index % len(plan.blocks)])
                key, at = divmod(block, plan.per_object)
                sent = plan.sent.setdefault(key, [])
                sent.append(block)
                op = Op(index, "write", key, len(sent), now)
            mine.append(op)
            try:
                with span("client-write"):
                    dep.client.write(dep.pool, plan.name(key),
                                     plan.patch(block),
                                     offset=at * plan.object_bytes)
                op.t_done = time.perf_counter()
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
