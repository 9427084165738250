"""Sub-object overwrites of an EC object, held to a plain reference.

An overwrite's parity arithmetic is the ordinary encode of its DELTA
STRIPE (the touched rows of the k data shards, zero but for old ^ new),
through ``_ec_encode`` and the batcher, on every back-end; the parity
shards are sent finished deltas and XOR them in
(``MSubPartialWrite.xor``).  The reference imports nothing of the
program: an image is its set-up bytes with every acknowledged
overwrite laid over them in order (``Image``), and what the stores
have to hold is ``benchmark/reference.py``'s numpy encoding of that.
Small sizes on the CPU platform, seeded data; nothing here is a
measurement.
"""

import threading

import numpy as np
import pytest

from benchmark import reference
from ceph_tpu.ec import registry
from ceph_tpu.ec.batcher import ECBatcher
from ceph_tpu.msg.messages import MSubPartialWrite, PgId
from ceph_tpu.osd.objectstore import CollectionId, ObjectId
from ceph_tpu.tools.vstart import MiniCluster
from ceph_tpu.utils import staging
from ceph_tpu.utils.config import FEATURES, ConfigError, default_config
from ceph_tpu.utils.tracked_op import OP_PHASES
from tests.test_cluster import make_cfg

UNIT = 4096
EAGAIN, ENOENT = -11, -2
PLANS = ("ec_plan_full_stripe", "ec_plan_parity_delta", "ec_plan_rmw")


# ------------------------------------------------------------ the reference
class Image:
    """Objects as plain bytes: what set-up wrote, with every
    acknowledged overwrite laid over it in the order it was sent."""

    def __init__(self, seed: int):
        self.rng = np.random.default_rng([seed, 0x6F77])
        self.objects: dict[str, bytearray] = {}

    def create(self, oid: str, size: int) -> bytes:
        self.objects[oid] = bytearray(self.rng.bytes(size))
        return bytes(self.objects[oid])

    def patch(self, length: int) -> bytes:
        return self.rng.bytes(length)

    def overwrite(self, oid: str, off: int, data: bytes) -> None:
        obj = self.objects[oid]
        if off + len(data) > len(obj):
            obj.extend(bytes(off + len(data) - len(obj)))
        obj[off:off + len(data)] = data


# ---------------------------------------------------------------- plumbing
class Bed:
    """A cluster, one EC pool on it, and the image beside it."""

    def __init__(self, k: int, m: int, backend: str, seed: int,
                 n_osds: int | None = None):
        self.k, self.m = k, m
        self.cluster = MiniCluster(
            n_osds=n_osds or k + m,
            cfg=make_cfg(ec_backend=backend)).start()
        self.client = self.cluster.client()
        self.pool_id = self.client.create_pool(
            "p", kind="ec", pg_num=4,
            ec_profile={"plugin": "tpu", "k": str(k), "m": str(m),
                        "backend": backend})
        self.image = Image(seed)

    def stop(self) -> None:
        self.cluster.stop()

    def place(self, oid: str):
        seed = self.client.osdmap.object_to_pg(self.pool_id, oid)
        up = list(self.client.osdmap.pg_to_up_osds(self.pool_id, seed))
        prim = self.cluster.osds[next(u for u in up if u is not None)]
        return prim, PgId(self.pool_id, seed), up

    def create(self, oid: str, size: int) -> None:
        self.client.write_full("p", oid, self.image.create(oid, size))

    def overwrite(self, oid: str, off: int, length: int) -> None:
        data = self.image.patch(length)
        self.client.write("p", oid, data, offset=off)
        self.image.overwrite(oid, off, data)

    def stored(self, oid: str) -> dict[int, bytes]:
        _prim, pgid, up = self.place(oid)
        cid = CollectionId(pgid.pool, pgid.seed)
        return {shard: self.cluster.osds[osd].store.read(
                    cid, ObjectId(oid, shard=shard)).to_bytes()
                for shard, osd in enumerate(up)
                if osd is not None and osd in self.cluster.osds}

    def count(self, name: str) -> int:
        return sum(o.perf.get(name) for o in self.cluster.osds.values())

    def counts(self, names=PLANS) -> dict[str, int]:
        return {n: self.count(n) for n in names}

    def check(self, oid: str) -> None:
        """The read-back is the image; every stored shard, parity
        included, is the reference's encoding of it."""
        want = bytes(self.image.objects[oid])
        assert self.client.read("p", oid) == want
        shards = reference.encode(want, self.k, self.m, UNIT)
        have = self.stored(oid)
        assert len(have) >= self.k
        for shard, data in have.items():
            assert data == shards[shard], (oid, shard)


def _grew(before: dict, after: dict) -> dict:
    return {n: after[n] - before[n] for n in after if after[n] != before[n]}


@pytest.fixture(scope="module", params=["jax", "native"])
def bed(request):
    b = Bed(4, 2, request.param, seed=38)
    yield b
    b.stop()


# ------------------------------------------------- the overwrite's shapes
ROW = 4 * UNIT
SIZE = 6 * ROW + 5000          # six whole rows and a ragged seventh

#: name -> (offset, length, the plan it has to take)
SHAPES = {
    "aligned_4k": (2 * ROW + 3 * UNIT, UNIT, "ec_plan_parity_delta"),
    "unaligned_in_one_chunk": (ROW + UNIT + 700, 1234,
                               "ec_plan_parity_delta"),
    "spanning_two_chunks": (3 * ROW + UNIT + 3000, 3000,
                            "ec_plan_parity_delta"),
    "spanning_two_rows": (4 * ROW - 2000, 5000, "ec_plan_parity_delta"),
    "ragged_tail_inside": (6 * ROW + 1000, 3000, "ec_plan_parity_delta"),
    # past the object's end, inside the last row's pad: the object grows
    "ragged_tail_growing": (SIZE - 100, 300, "ec_plan_parity_delta"),
    # whole rows need no old bytes
    "whole_rows": (ROW, 2 * ROW, "ec_plan_full_stripe"),
    # growth into rows that do not exist yet: no delta against nothing
    "growing_into_new_rows": (SIZE - 50, 2 * ROW, "ec_plan_rmw"),
}


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_overwrite_shapes(bed, shape):
    off, length, plan = SHAPES[shape]
    oid = f"obj-{shape}"
    bed.create(oid, SIZE)
    falls = staging.fallthrough_counts()
    before = bed.counts()
    bed.overwrite(oid, off, length)
    assert _grew(before, bed.counts()) == {plan: 1}
    bed.check(oid)
    # and a second one over part of the first: deltas fold, not replace
    bed.overwrite(oid, off + length // 2, length)
    bed.check(oid)
    assert staging.fallthrough_counts() == falls


def test_sixteen_writers_on_distinct_blocks_of_one_object(bed):
    oid = "obj-sixteen"
    bed.create(oid, SIZE)
    prim, _pgid, _up = bed.place(oid)
    blocks = bed.image.rng.permutation(SIZE // UNIT)[:16]
    patches = {int(b): bed.image.patch(UNIT) for b in blocks}
    before = bed.counts(PLANS + ("ec_ow_subwrites",))
    launches = prim._ec_batcher.stats["launches"]
    clients = [bed.cluster.client() for _ in patches]
    errors = []

    def write(client, block):
        try:
            client.write("p", oid, patches[block], offset=block * UNIT)
        except Exception as e:  # noqa: BLE001 - reported below
            errors.append(e)

    threads = [threading.Thread(target=write, args=(c, b))
               for c, b in zip(clients, patches)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert errors == []
    for block, data in patches.items():     # distinct: any order
        bed.image.overwrite(oid, block * UNIT, data)
    grew = _grew(before, bed.counts(PLANS + ("ec_ow_subwrites",)))
    assert grew == {"ec_plan_parity_delta": 16,
                    "ec_ow_subwrites": 16 * 5}
    # one object, one order: its overwrites are served one at a time,
    # at most one launch each
    assert prim._ec_batcher.stats["launches"] - launches <= 16
    bed.check(oid)


def test_an_overwrite_is_one_launch_and_no_host_multiply(bed, monkeypatch):
    """The coefficient multiply goes through ``_ec_encode`` and the
    batcher, once, and nowhere else: neither the plugin's per-shard
    fold nor a region multiply-accumulate runs on an overwrite's path."""
    from ceph_tpu.ec.matrix_code import MatrixErasureCode
    from ceph_tpu.ops import native
    from ceph_tpu.osd.daemon import OSDDaemon
    oid = "obj-one-launch"
    bed.create(oid, SIZE)
    prim, pgid, _up = bed.place(oid)
    calls = []
    real = OSDDaemon._ec_encode

    def seen(self, codec, streams, with_csums, m=None):
        calls.append((self.osd_id, np.asarray(streams).shape))
        return real(self, codec, streams, with_csums, m)

    def never(*_a, **_kw):
        raise AssertionError("a per-shard GF multiply on the host")

    monkeypatch.setattr(OSDDaemon, "_ec_encode", seen)
    monkeypatch.setattr(MatrixErasureCode, "apply_delta", never)
    monkeypatch.setattr(native, "region_mac", never)
    jax_pool = prim._pool_codec(bed.pool_id)._backend == "jax"
    stats = dict(prim._ec_batcher.stats)
    bed.overwrite(oid, 5 * ROW + 2 * UNIT, UNIT)
    # the delta stripe: one row of the k data shards
    assert calls == [(prim.osd_id, (4, UNIT))]
    if jax_pool:
        assert prim._ec_batcher.stats["launches"] - stats["launches"] == 1
        assert prim._ec_batcher.stats["ops"] - stats["ops"] == 1
    bed.check(oid)


def test_timeline_books_the_old_byte_read_and_the_sub_writes(bed):
    oid = "obj-timeline"
    bed.create(oid, SIZE)
    prim, pgid, up = bed.place(oid)
    # a block on a shard the primary does not hold, its old bytes not
    # in the primary's extent cache: a sub-read goes out and comes back
    shard = next(s for s in range(bed.k) if up[s] != prim.osd_id)
    prim._ec_cache.invalidate(pgid, oid)
    names = [f"op_phase_{p}" for p in OP_PHASES] + ["op_timeline"]

    def times():
        d = prim.perf.dump()
        return {n: (d[n]["sum_seconds"], d[n]["count"]) for n in names}

    t0 = times()
    before = bed.counts(("ec_ow_subreads", "ec_ow_subwrites",
                         "ec_ow_old_cached"))
    bed.overwrite(oid, 2 * ROW + shard * UNIT, UNIT)
    t1 = times()
    spent = {n: t1[n][0] - t0[n][0] for n in names}
    assert all(t1[n][1] - t0[n][1] == 1 for n in names)
    assert spent["op_phase_subread_wait"] > 0
    assert spent["op_phase_subwrite_wait"] > 0
    assert sum(spent[f"op_phase_{p}"] for p in OP_PHASES) == \
        pytest.approx(spent["op_timeline"], rel=1e-9)
    op = next(d for d in reversed(prim.admin_command("dump_historic_ops"))
              if d["description"] == f"write {oid}")
    marks = [e["event"] for e in op["events"] if e["event"] != "ec_queued"]
    assert marks == [
        "initiated", "queued_for_pg", "reached_pg",
        "waiting_for_obj_lock", "started", "waiting_for_subreads",
        "sub_reads_rec", "ec_taken", "ec_done", "waiting_for_subops",
        "sub_op_commit_rec", "commit_sent", "done"]
    # one sub-read; a sub-write to each of the five other shards (new
    # bytes, two parity deltas, two version stamps)
    assert _grew(before, bed.counts(tuple(before))) == {
        "ec_ow_subreads": 1, "ec_ow_subwrites": 5}
    # the next overwrite of the block finds its old bytes in the cache
    bed.overwrite(oid, 2 * ROW + shard * UNIT, UNIT)
    assert bed.count("ec_ow_old_cached") == before["ec_ow_old_cached"] + 1
    assert bed.count("ec_ow_subreads") == before["ec_ow_subreads"] + 1
    bed.check(oid)


def test_parity_shard_refuses_a_delta_against_another_version(bed):
    """The ``prev_version`` condition holds for the XOR form as for the
    plain one, and a delta is never folded into a shard that is not
    there."""
    oid = "obj-refuse"
    bed.create(oid, 2 * ROW)
    _prim, pgid, up = bed.place(oid)
    holder = bed.cluster.osds[up[bed.k]]          # the first parity shard
    have = bed.stored(oid)[bed.k]
    delta = [(0, bytes([0xFF]) * UNIT)]
    assert holder._apply_partial(pgid, oid, bed.k, delta, 99,
                                 prev_version=12345, xor=True) == EAGAIN
    assert holder._apply_partial(pgid, "absent", bed.k, delta, 99,
                                 xor=True) == ENOENT
    assert bed.stored(oid)[bed.k] == have
    bed.check(oid)


# -------------------------------------------------- other codes, one each
@pytest.mark.parametrize("k,m", [(8, 3), (2, 1)])
def test_other_profiles_on_the_jax_backend(k, m):
    b = Bed(k, m, "jax", seed=100 * k + m)
    try:
        size = 3 * k * UNIT + 777
        b.create("obj", size)
        before = b.counts()
        b.overwrite("obj", k * UNIT + (k - 1) * UNIT, UNIT)   # aligned
        b.overwrite("obj", 2 * k * UNIT - 1500, 4000)         # two rows
        b.overwrite("obj", 3 * k * UNIT + 100, 500)           # the tail
        assert _grew(before, b.counts()) == {"ec_plan_parity_delta": 3}
        b.check("obj")
        assert all(v == 0 for v in staging.fallthrough_counts().values())
    finally:
        b.stop()


# ------------------------------------------------------ one OSD stopped
@pytest.mark.parametrize("backend", ["jax", "native"])
def test_with_an_osd_stopped_an_overwrite_takes_row_rmw(backend):
    b = Bed(4, 2, backend, seed=7)
    try:
        b.create("obj", SIZE)
        prim, _pgid, up = b.place("obj")
        victim = next(u for u in up if u != prim.osd_id)
        epoch = b.cluster.mon.osdmap.epoch
        b.cluster.kill_osd(victim)
        b.cluster.wait_for_epoch(epoch + 1, timeout=10)
        b.cluster.settle(0.5)
        assert None in b.place("obj")[2]
        before = b.counts()
        b.overwrite("obj", 2 * ROW + UNIT, UNIT)
        b.overwrite("obj", 5 * ROW - 700, 2000)
        assert _grew(before, b.counts()) == {"ec_plan_rmw": 2}
        b.check("obj")
    finally:
        b.stop()


# ------------------------------------------------------------- the pieces
def test_plugin_fold_on_a_jax_pool_is_a_counted_fall_through(request):
    """``apply_delta`` stays the plugin interface's host fold; on a jax
    pool it is a fall-through (raised on an accelerator, counted
    here), and its bytes are the native back-end's."""
    rng = np.random.default_rng(5)
    delta = rng.integers(0, 256, 2048, dtype=np.uint8)
    out = {}
    # the counter is the process's: other files of this worker read it
    name = "ec_delta_host_fallback"
    request.addfinalizer(lambda held=staging.fallthrough_counts()[name]:
                         staging.stage_perf().set(name, held))
    for backend in ("native", "jax"):
        codec = registry.factory("tpu", {"k": "4", "m": "2",
                                         "backend": backend})
        parity = {4: np.zeros(2048, np.uint8), 5: np.ones(2048, np.uint8)}
        before = staging.fallthrough_counts()["ec_delta_host_fallback"]
        codec.apply_delta(delta, 2, parity)
        counted = staging.fallthrough_counts()["ec_delta_host_fallback"]
        assert counted - before == (backend == "jax")
        out[backend] = parity
    for pid in (4, 5):
        assert np.array_equal(out["jax"][pid], out["native"][pid])
    # the same bytes as the encode of the delta stripe, by linearity
    stripe = np.zeros((4, 2048), np.uint8)
    stripe[2] = delta
    enc = registry.factory("tpu", {"k": "4", "m": "2", "backend": "native"}
                           ).encode_chunks(stripe)
    assert np.array_equal(out["native"][4], enc[0])
    assert np.array_equal(out["native"][5], enc[1] ^ 1)


def test_a_pool_written_whole_expects_its_row_bucket(monkeypatch):
    """On an accelerator the first whole-object write of a pool makes
    the batcher compile the one-row bucket's encodes, so an overwrite
    meets no compile; the decodes wait for the bucket's first op."""
    from ceph_tpu.ec import batcher as mod
    warmed = []
    monkeypatch.setattr(ECBatcher, "_stages_on_ingest",
                        staticmethod(lambda codec: True))
    monkeypatch.setattr(
        ECBatcher, "_warm_bucket",
        lambda self, codec, length, decodes=True:
        warmed.append((length, decodes)))
    monkeypatch.setattr(mod, "_WARM_CLAIMED", set())
    monkeypatch.setattr(mod, "_WARM_THREADS", [])
    codec = registry.factory("tpu", {"k": "4", "m": "2",
                                     "backend": "jax"})
    b = ECBatcher()
    b.expect(codec, UNIT)
    b.expect(codec, UNIT)                       # claimed: nothing
    assert ECBatcher.warm_wait(10) and warmed == [(UNIT, False)]
    sig = ("enc", codec.fold_sig(), codec.matrix.tobytes(), 4, 2, True,
           mod.bucket_len(UNIT))
    b._warm_bucket_once(codec, UNIT, sig)       # an overwrite's encode:
    assert ECBatcher.warm_wait(10) and len(warmed) == 1   # nothing more
    dec = ("dec",) + sig[1:5] + ((1, 2, 3, 4), (0,), sig[-1])
    b._warm_bucket_once(codec, UNIT, dec)       # the bucket's first decode
    b._warm_bucket_once(codec, UNIT, dec)
    b._warm_bucket_once(codec, UNIT, sig)
    b.expect(codec, UNIT)                       # whole bucket claimed
    assert ECBatcher.warm_wait(10)
    assert warmed == [(UNIT, False), (UNIT, True)]
    # a bucket nobody expected: its first encode warms all of it
    b._warm_bucket_once(codec, 2 * UNIT, sig[:-1] + (2 * UNIT,))
    b.expect(codec, 2 * UNIT)
    assert ECBatcher.warm_wait(10)
    assert warmed[2:] == [(2 * UNIT, True)]
    ECBatcher(window_us=0).expect(codec, 4 * UNIT)   # pass-through
    assert ECBatcher.warm_wait(10) and len(warmed) == 3


def test_feature_is_named_for_deployment_files():
    assert "ec_overwrite_on_device" in FEATURES
    cfg = default_config()
    cfg.apply_dict({"require_features": "ec_overwrite_on_device"})
    with pytest.raises(ConfigError):
        default_config().apply_dict(
            {"require_features": "ec_overwrite_on_the_moon"})


def test_partial_write_message_defaults_to_the_plain_form():
    m = MSubPartialWrite(1, PgId(1, 2), "o", 4, 9, [(0, b"x")])
    assert m.xor is False
