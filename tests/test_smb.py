"""SMB gateway (the smb-over-CephFS role): an SMB2 (dialect 2.0.2,
guest auth) server exporting fs trees as shares, driven by the in-repo
client over real sockets — the NBD/NVMe gateway pattern."""

import numpy as np
import pytest

from ceph_tpu.services.smb import SmbClient, SmbServer
from ceph_tpu.tools.vstart import MiniCluster
from tests.test_cluster import make_cfg

RNG = np.random.default_rng(41)


@pytest.fixture
def smb():
    c = MiniCluster(n_osds=4, cfg=make_cfg()).start()
    c.client().create_pool("fsp", size=2, pg_num=4)
    srv = SmbServer(lambda: c.client())
    srv.add_share("docs", "fsp")
    yield c, srv
    srv.stop()
    c.stop()


def test_negotiate_session_tree(smb):
    c, srv = smb
    cl = SmbClient("127.0.0.1", srv.port)
    try:
        assert cl.dialect == 0x0202
        assert cl.sid >= 0x100
        cl.tree_connect("docs")
        assert cl.tid >= 1
        cl2 = SmbClient("127.0.0.1", srv.port)
        try:
            with pytest.raises(AssertionError):
                cl2.tree_connect("nope")
        finally:
            cl2.close()
    finally:
        cl.close()


def test_file_io_roundtrip(smb):
    c, srv = smb
    cl = SmbClient("127.0.0.1", srv.port)
    try:
        cl.tree_connect("docs")
        d = cl.mkdir("reports")
        cl.close_file(d)
        f = cl.create_file("reports/q3.bin")
        data = RNG.integers(0, 256, 300_000, dtype=np.uint8).tobytes()
        cl.write(f, 0, data[:200_000])
        cl.write(f, 200_000, data[200_000:])
        cl.close_file(f)
        f = cl.open("reports/q3.bin")
        got = b""
        off = 0
        while off < len(data):
            chunk = cl.read(f, off, 65536)
            if not chunk:
                break
            got += chunk
            off += len(chunk)
        assert got == data
        cl.close_file(f)
        # the same bytes are visible through a direct fs mount
        from ceph_tpu.services.fs import FsClient
        fs = FsClient(c.client(), "fsp")
        assert fs.read_file("/reports/q3.bin") == data
        fs.write_file("/reports/q3.bin", b"PATCH", offset=10)
        fs.unmount()
        f = cl.open("reports/q3.bin")
        assert cl.read(f, 10, 5) == b"PATCH"
        cl.close_file(f)
    finally:
        cl.close()


def test_directory_listing_and_delete(smb):
    c, srv = smb
    cl = SmbClient("127.0.0.1", srv.port)
    try:
        cl.tree_connect("docs")
        cl.close_file(cl.mkdir("a"))
        cl.close_file(cl.create_file("a/x.txt"))
        f = cl.create_file("a/y.txt")
        cl.write(f, 0, b"hello")
        cl.close_file(f)
        root = cl.open("/")
        names = {e["name"]: e for e in cl.listdir(root)}
        cl.close_file(root)
        assert set(names) == {"a"} and names["a"]["dir"]
        d = cl.open("a")
        entries = {e["name"]: e for e in cl.listdir(d)}
        cl.close_file(d)
        assert set(entries) == {"x.txt", "y.txt"}
        assert entries["y.txt"]["size"] == 5
        assert not entries["x.txt"]["dir"]
        # delete-on-close removes the file
        f = cl.open("a/x.txt")
        cl.close_file(f, delete=True)
        d = cl.open("a")
        assert [e["name"] for e in cl.listdir(d)] == ["y.txt"]
        cl.close_file(d)
        # open of the deleted file now refuses
        with pytest.raises(OSError):
            cl.open("a/x.txt")
    finally:
        cl.close()


def test_create_semantics(smb):
    c, srv = smb
    cl = SmbClient("127.0.0.1", srv.port)
    try:
        cl.tree_connect("docs")
        cl.close_file(cl.create_file("f1"))
        with pytest.raises(OSError):   # FILE_CREATE collides
            cl.create_file("f1")
        with pytest.raises(OSError):   # FILE_OPEN of absent
            cl.open("missing")
        # share control plane
        assert srv.list_shares() == ["docs"]
        srv.remove_share("docs")
        cl2 = SmbClient("127.0.0.1", srv.port)
        try:
            with pytest.raises(AssertionError):
                cl2.tree_connect("docs")
        finally:
            cl2.close()
    finally:
        cl.close()


def test_enumeration_cursor_and_disconnect_delete(smb):
    """Conformant-client behaviors: repeated QUERY_DIRECTORY ends with
    STATUS_NO_MORE_FILES (no infinite duplicate listings), and a
    dropped connection still fires pending delete-on-close."""
    c, srv = smb
    cl = SmbClient("127.0.0.1", srv.port)
    try:
        cl.tree_connect("docs")
        cl.close_file(cl.create_file("once"))
        root = cl.open("/")
        assert [e["name"] for e in cl.listdir(root)] == ["once"]
        assert cl.listdir(root) == []     # cursor exhausted
        cl.close_file(root)
        # mark for deletion, then DROP the connection without CLOSE
        f = cl.open("once")
        payload = __import__("struct").pack(
            "<HBBIHHI", 33, 1, 13, 1, 64 + 32, 0, 0) + f + b"\x01"
        st, _h, _b = cl._cmd(0x11, payload)
        assert st == 0
    finally:
        cl.close()                        # disconnect fires the delete
    import time as _t
    deadline = _t.time() + 5
    while _t.time() < deadline:
        cl3 = SmbClient("127.0.0.1", srv.port)
        try:
            cl3.tree_connect("docs")
            root = cl3.open("/")
            try:
                names = [e["name"] for e in cl3.listdir(root)]
            except AssertionError:
                # the listing met the entry mid-delete
                # (STATUS_OBJECT_NAME_NOT_FOUND): look again
                names = ["once"]
            cl3.close_file(root)
            if "once" not in names:
                return
        finally:
            cl3.close()
        _t.sleep(0.1)
    raise AssertionError("delete-on-close never fired on disconnect")
