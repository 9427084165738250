"""Wire-format non-regression gate (ceph-dencoder + ceph-object-corpus
role, ref src/tools/ceph-dencoder/): archived encoded bytes of every
message/struct must keep decoding — the rolling-restart contract no
in-suite exchange can test, because both ends always run today's code.
"""

import os
import shutil

import ceph_tpu
from ceph_tpu.tools import dencoder

REPO = os.path.dirname(os.path.dirname(os.path.abspath(
    ceph_tpu.__file__)))
CORPUS = os.path.join(REPO, "corpus_wire")


def test_corpus_covers_every_wire_type():
    from ceph_tpu.msg.wire import MESSAGE_TYPES
    have = set(os.listdir(CORPUS))
    for cls in MESSAGE_TYPES:
        assert f"msg_{cls.__name__}.bin" in have, \
            f"{cls.__name__} added to the wire registry without " \
            f"archiving its bytes (run dencoder --create)"
    for name in dencoder.struct_samples():
        assert f"struct_{name}.bin" in have


def test_archived_bytes_still_decode():
    problems = dencoder.check(CORPUS)
    assert problems == []


def _copy_corpus(tmp_path) -> str:
    dst = str(tmp_path / "corpus_wire")
    shutil.copytree(CORPUS, dst)
    return dst


def test_gate_catches_incompatible_version_bump(tmp_path):
    """A blob whose encoder demanded a NEWER compat than we support
    (the downgrade/rolling-restart hazard) must be reported."""
    base = _copy_corpus(tmp_path)
    path = os.path.join(base, "struct_PoolSpec.bin")
    raw = bytearray(open(path, "rb").read())
    raw[1] = 99  # compat byte: "you need at least v99 to read this"
    open(path, "wb").write(bytes(raw))
    problems = dencoder.check(base)
    assert any("PoolSpec" in p and "no longer decode" in p
               for p in problems), problems


def test_gate_catches_field_drift(tmp_path):
    """Archived bytes that DECODE but no longer reproduce the canonical
    fields (a silently re-ordered/re-typed field) must be reported."""
    base = _copy_corpus(tmp_path)
    path = os.path.join(base, "msg_MOSDOp.bin")
    raw = open(path, "rb").read()
    assert b"obj" in raw
    open(path, "wb").write(raw.replace(b"obj", b"obX", 1))
    problems = dencoder.check(base)
    assert any("MOSDOp" in p and "differ" in p for p in problems), \
        problems


def test_gate_catches_missing_archive(tmp_path):
    base = _copy_corpus(tmp_path)
    os.remove(os.path.join(base, "msg_MAuth.bin"))
    problems = dencoder.check(base)
    assert any("MAuth" in p and "no archived blob" in p
               for p in problems), problems


def test_partial_write_carries_a_finished_parity_delta():
    """The parity leg of a parity-delta overwrite rides
    ``MSubPartialWrite`` with ``xor`` set (the shard XORs the extents
    into what it holds): the form round-trips, has its own archived
    blob, and the bytes archived before the field existed decode as the
    plain form."""
    from ceph_tpu.msg import messages as M
    from ceph_tpu.msg.wire import decode_frame, encode_frame
    sample = dencoder.variant_samples()["MSubPartialWrite.xor"]
    assert sample.xor is True
    raw = encode_frame("osd.0", "osd.1", sample)
    src, dst, got = decode_frame(raw[4:])
    assert (src, dst, got) == ("osd.0", "osd.1", sample)
    with open(os.path.join(CORPUS, "msg_MSubPartialWrite.xor.bin"),
              "rb") as f:
        _s, _d, archived = decode_frame(f.read()[4:])
    assert archived == sample
    with open(os.path.join(CORPUS, "msg_MSubPartialWrite.bin"),
              "rb") as f:
        _s, _d, old = decode_frame(f.read()[4:])
    assert type(old) is M.MSubPartialWrite and old.xor is False
    assert old.extents == [(0, b"ab"), (4096, b"cd")]


def test_gate_catches_a_missing_variant(tmp_path):
    base = _copy_corpus(tmp_path)
    os.remove(os.path.join(base, "msg_MSubPartialWrite.xor.bin"))
    problems = dencoder.check(base)
    assert any("MSubPartialWrite.xor" in p and "no archived blob" in p
               for p in problems), problems


def test_retired_wire_id_is_not_reused():
    """Id 5 was MSubDelta (raw data deltas, multiplied on the parity
    shard's host); it went with the delta-stripe encode.  The ids after
    it did not move and 5 decodes to nothing."""
    import pytest

    from ceph_tpu.msg import messages as M
    from ceph_tpu.utils.codec import CodecError
    from ceph_tpu.msg.wire import (_ID_TYPES, _TYPE_IDS, MESSAGE_TYPES,
                                   decode_frame, encode_frame)
    assert 5 not in _ID_TYPES and not hasattr(M, "MSubDelta")
    assert _TYPE_IDS[M.MSubPartialWrite] == 4
    assert _TYPE_IDS[M.MSubWriteReply] == 6
    assert _TYPE_IDS[M.MLeaseRegister] == 47
    assert len(MESSAGE_TYPES) == len(_TYPE_IDS) == 46
    raw = bytearray(encode_frame("a", "b", M.MSubWriteReply(
        1, M.PgId(1, 2), 0, 3)))
    at = raw.index((6).to_bytes(2, "little"), 4)
    raw[at:at + 2] = (5).to_bytes(2, "little")
    with pytest.raises(CodecError):
        decode_frame(bytes(raw[4:]))
