"""Storage adaptors: uniform semantics across heterogeneous backends."""

import pytest

from repro.backends import (
    KeyNotFound,
    MemoryBackend,
    ObjectStoreBackend,
    StorageError,
    available_schemes,
    make_backend,
)


@pytest.fixture(params=["mem", "file", "sharedfs", "object"])
def backend(request, tmp_path, monkeypatch):
    import repro.backends.local_fs as lfs

    monkeypatch.setattr(lfs, "_SANDBOX", str(tmp_path))
    url = {
        "mem": "mem://hostA/c1",
        "file": "file://hostA/c1",
        "sharedfs": "sharedfs://siteA/scratch",
        "object": "object://region1/bucket1",
    }[request.param]
    # unique container per test to avoid cross-test shared-store state
    return make_backend(url + f"-{request.node.name}")


def test_put_get_roundtrip(backend):
    assert backend.put("k1", b"hello") == 5
    assert backend.get("k1") == b"hello"
    assert backend.exists("k1")
    assert backend.size("k1") == 5


def test_hierarchical_keys(backend):
    backend.put("a/b/c.bin", b"x" * 10)
    assert backend.get("a/b/c.bin") == b"x" * 10
    assert backend.list() == (
        ["a%2Fb%2Fc.bin"] if backend.flat_namespace else ["a/b/c.bin"]
    )


def test_delete_and_missing(backend):
    backend.put("k", b"1")
    backend.delete("k")
    assert not backend.exists("k")
    with pytest.raises(KeyNotFound):
        backend.get("k")
    backend.delete("k")  # idempotent


@pytest.mark.parametrize("skip,size", [(0, 10), (3, 4), (6, 10), (10, 2)])
def test_get_into(backend, skip, size):
    """Bytes [skip, skip + size) land in the buffer; fewer past the end."""
    backend.put("g/k", b"0123456789")
    buf = bytearray(b"#" * size)
    got = backend.get_into("g/k", memoryview(buf), skip)
    want = b"0123456789"[skip : skip + size]
    assert got == len(want) and bytes(buf[:got]) == want
    assert buf[got:] == b"#" * (size - got)
    with pytest.raises(KeyNotFound):
        backend.get_into("g/missing", memoryview(buf))
    with pytest.raises(KeyNotFound):
        backend.size("g/missing")


def test_list_prefix(backend):
    if backend.flat_namespace:
        pytest.skip("flat namespace encodes separators")
    backend.put("x/1", b"a")
    backend.put("x/2", b"b")
    backend.put("y/1", b"c")
    assert backend.list("x/") == ["x/1", "x/2"]


def test_key_validation(backend):
    for bad in ("", "/abs", "a/../b"):
        with pytest.raises(ValueError):
            backend.put(bad, b"x")


def test_object_store_write_once():
    b = ObjectStoreBackend("object://region1/wonce")
    b.put("k", b"v1")
    with pytest.raises(StorageError):
        b.put("k", b"v2")
    bv = ObjectStoreBackend("object://region1/wonce-v", versioning=True)
    bv.put("k", b"v1")
    bv.put("k", b"v2")
    assert bv.get("k") == b"v2"


def test_mem_backend_shared_by_url():
    a = MemoryBackend("mem://h/shared1")
    b = MemoryBackend("mem://h/shared1")
    a.put("k", b"v")
    assert b.get("k") == b"v"  # same container → same data (shared FS model)
    c = MemoryBackend("mem://h/other")
    assert not c.exists("k")


def test_registry_and_profiles():
    assert set(available_schemes()) >= {"mem", "file", "sharedfs", "object"}
    with pytest.raises(ValueError):
        make_backend("bogus://x/y")
    fast = make_backend("mem://h/p1")
    slow = make_backend("object://r/b1")
    assert fast.profile.bandwidth > slow.profile.bandwidth
    assert fast.simulated_put_time(1 << 30) < slow.simulated_put_time(1 << 30)


def test_scheme_mismatch_raises():
    with pytest.raises(ValueError):
        MemoryBackend("file://h/c")
