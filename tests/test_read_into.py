"""The DU read path: ``fetch_du_file`` assembles a file in one buffer
through ``StorageAdaptor.get_into``, ``decode_array`` returns checkpoint
leaves as views of it, and ``du.read`` says which path a read took."""

import ml_dtypes
import numpy as np
import pytest

from repro import tracing
from repro.backends import KeyNotFound, chunk_key
from repro.checkpoint import decode_array, encode_array
from repro.core import (
    FUNCTIONS,
    DataUnit,
    DataUnitDescription,
    PilotManager,
    Session,
    Topology,
    make_tpu_fleet_topology,
)

CHUNK = 64

#: the canonical stream is the sorted relpaths, so with 64-byte chunks:
#: ``a`` [0, 10) ends mid-chunk, ``b`` [10, 40) lies inside chunk 0,
#: ``c`` [40, 340) starts and ends mid-chunk across chunks 0-5,
#: ``d`` is empty, ``e`` [340, 384) ends on the last chunk's end
FILES = {
    "a": bytes(range(10)),
    "b": bytes(range(100, 130)),
    "c": bytes(i * 7 % 251 for i in range(300)),
    "d": b"",
    "e": b"e" * 44,
}


@pytest.fixture()
def storage_root(tmp_path, monkeypatch):
    import repro.backends.local_fs as lfs

    monkeypatch.setattr(lfs, "_SANDBOX", str(tmp_path))


def _pd_with_du(mgr, scheme):
    pd = mgr.start_pilot_data(
        service_url=f"{scheme}://site:a/read-into-{scheme}", affinity="site:a"
    )
    du = DataUnit(
        DataUnitDescription(name="r", files=FILES, chunk_size=CHUNK), mgr.store
    )
    pd.put_du(du)
    return pd, du


def _topo():
    t = Topology()
    t.register("site:a", bandwidth=30e6, latency=0.05)
    return t


@pytest.mark.parametrize("relpath", ["a", "b", "c", "d", "e"])
@pytest.mark.parametrize("scheme", ["file", "sharedfs", "mem"])
def test_fetch_du_file_matches_du_read(storage_root, scheme, relpath):
    with PilotManager(topology=_topo()) as mgr:
        pd, du = _pd_with_du(mgr, scheme)
        got = pd.fetch_du_file(du.id, relpath)
        assert isinstance(got, bytearray)
        assert got == du.read(relpath) == FILES[relpath]


@pytest.mark.parametrize("scheme", ["file", "sharedfs", "mem"])
def test_fetch_du_file_missing_chunk(storage_root, scheme):
    with PilotManager(topology=_topo()) as mgr:
        pd, du = _pd_with_du(mgr, scheme)
        assert 2 in du.chunks_for_file("c")
        pd.backend.delete(chunk_key(du.id, 2))
        with pytest.raises(KeyNotFound):
            pd.fetch_du_file(du.id, "c")
        assert pd.fetch_du_file(du.id, "a") == FILES["a"]  # chunk 0 still there


# ------------------------------------------------------------ decode_array
DTYPES = [np.float32, ml_dtypes.bfloat16, ml_dtypes.float8_e4m3fn, np.int8]
SHAPES = [(3, 5), (2, 3, 4), (), (0, 4)]


def _leaf(dtype, shape, order):
    rng = np.random.default_rng(7)
    a = (rng.standard_normal(shape) * 4).astype(dtype)
    return np.asfortranarray(a) if order == "F" else np.ascontiguousarray(a)


@pytest.mark.parametrize("shape", SHAPES, ids=str)
@pytest.mark.parametrize("order", ["C", "F"])
@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: np.dtype(d).name)
def test_decode_array_round_trip_bit_equal(dtype, order, shape):
    a = _leaf(dtype, shape, order)
    enc = encode_array(a)
    for data in (enc, bytearray(enc)):
        b = decode_array(data)
        assert b.dtype == a.dtype and b.shape == a.shape
        assert b.tobytes(order="A") == a.tobytes(order="A")
        assert b.flags.f_contiguous == a.flags.f_contiguous
        assert b.flags.c_contiguous == a.flags.c_contiguous


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: np.dtype(d).name)
def test_decode_array_views_a_bytearray(dtype):
    buf = bytearray(encode_array(_leaf(dtype, (4, 6), "C")))
    leaf = decode_array(buf)
    assert np.shares_memory(leaf, np.frombuffer(buf, np.uint8))
    assert leaf.flags.writeable


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: np.dtype(d).name)
def test_decode_array_from_bytes_is_writable(dtype):
    a = _leaf(dtype, (4, 6), "F")
    leaf = decode_array(encode_array(a))
    assert leaf.flags.writeable
    leaf[0, 0] = 1
    assert leaf[0, 0] == 1


# ------------------------------------------------------- du.read's path
@pytest.mark.parametrize("scheme,path", [("sharedfs", "into"), ("mem", "copy")])
def test_chunks_read_into_counter(storage_root, scheme, path):
    """A linked read of a pod's Pilot-Data: every chunk of a sharedfs://
    one is read into the buffer, none of a mem:// one."""
    exe = f"read-into-{scheme}"
    payload = bytes(i % 253 for i in range(5000))

    @FUNCTIONS.register(exe)
    def read(cu_ctx, du_id):
        return cu_ctx.read_input(du_id, "part.bin")

    topo, hosts = make_tpu_fleet_topology(pods=1, hosts_per_pod=1)
    s = Session(topology=topo)
    with s:
        s.start_pilot_data(
            service_url=f"{scheme}://cluster:pod0/read-into",
            affinity="cluster:pod0",
        )
        s.start_pilot(resource_url=f"sim://{hosts[0]}", slots=1)
        du = s.submit_du(
            name="in",
            files={"part.bin": payload},
            chunk_size=CHUNK,
        )
        before = tracing.counters()
        fut = s.submit_cu(executable=exe, args=(du.id,), input_data=[du])
        assert fut.result(timeout=60) == payload
        after = tracing.counters()
    rec = next(
        r for r in tracing.spans() if r.cu == fut.cu.id and r.name == "du.read"
    )
    assert rec.attrs["via"] == "link" and rec.attrs["path"] == path
    chunks = rec.attrs["chunks"]
    assert chunks == -(-len(payload) // CHUNK)

    def delta(name):
        return after.get(name, 0) - before.get(name, 0)

    assert delta("du.chunks_read") == chunks
    assert delta("du.chunks_read_into") == (chunks if path == "into" else 0)
