"""Checkpointer-as-DU + data pipeline tests."""

import threading
import time

import numpy as np
import pytest

from repro.checkpoint import (
    Checkpointer,
    CheckpointError,
    CheckpointTimeout,
)
from repro.core import (
    DUState,
    PilotManager,
    make_tpu_fleet_topology,
)
from repro.data import (
    Prefetcher,
    ShardReader,
    decode_raw_tokens,
    decode_tokens,
    encode_raw_tokens,
    encode_tokens,
    make_token_shards,
    shard_dus,
)


@pytest.fixture()
def mgr():
    topo, _ = make_tpu_fleet_topology(pods=2, hosts_per_pod=2)
    m = PilotManager(topology=topo)
    yield m
    m.shutdown()


@pytest.fixture()
def healing_mgr():
    topo, _ = make_tpu_fleet_topology(pods=2, hosts_per_pod=2)
    m = PilotManager(topology=topo, enable_fault_manager=True, heartbeat_timeout_s=0.5)
    yield m
    m.shutdown()


def test_token_roundtrip():
    t = np.arange(100, dtype=np.int32)
    assert (decode_tokens(encode_tokens(t)) == t).all()


def test_raw_token_roundtrip_and_prefix_decode():
    t = np.arange(100, dtype=np.int32)
    data = encode_raw_tokens(t)
    assert (decode_raw_tokens(data) == t).all()
    # any byte prefix decodes to a token prefix (the chunk-stream property)
    assert (decode_raw_tokens(data[: 4 * 17]) == t[:17]).all()
    assert (decode_raw_tokens(data[: 4 * 17 + 3]) == t[:17]).all()


def test_make_token_shards_shapes():
    shards = make_token_shards(3, 1000, vocab_size=50, files_per_shard=2)
    assert len(shards) == 3
    for files in shards:
        assert len(files) == 2
        total = sum(len(decode_tokens(d)) for d in files.values())
        assert total == 1000
        for d in files.values():
            toks = decode_tokens(d)
            assert toks.min() >= 0 and toks.max() < 50


def test_make_token_shards_raw_format():
    shards = make_token_shards(2, 800, vocab_size=32, fmt="raw")
    for files in shards:
        assert all(rel.endswith(".bin") for rel in files)
        total = sum(len(decode_raw_tokens(d)) for d in files.values())
        assert total == 800
    with pytest.raises(ValueError):
        make_token_shards(1, 100, vocab_size=8, fmt="parquet")


def test_shard_reader_batches():
    shards = make_token_shards(1, 2000, vocab_size=64)
    reader = ShardReader(shards[0], seed=1)
    it = reader.batches(batch=4, seq=32)
    b1 = next(it)
    assert b1["tokens"].shape == (4, 32) and b1["labels"].shape == (4, 32)
    # next-token alignment
    assert (b1["labels"][:, :-1] == b1["tokens"][:, 1:]).all()


def test_shard_reader_resume_matches_continuation():
    """batches(start_step=k) replays the SAME data an uninterrupted run
    sees at step k — the checkpoint/restart determinism contract."""
    shards = make_token_shards(1, 3000, vocab_size=64)
    full = ShardReader(shards[0], seed=7).batches(batch=2, seq=16)
    straight = [next(full) for _ in range(6)]
    resumed_it = ShardReader(shards[0], seed=7).batches(batch=2, seq=16, start_step=3)
    resumed = [next(resumed_it) for _ in range(3)]
    for a, b in zip(straight[3:], resumed):
        np.testing.assert_array_equal(a["tokens"], b["tokens"])
        np.testing.assert_array_equal(a["labels"], b["labels"])


def test_prefetcher_order_and_close():
    pf = Prefetcher(iter(range(10)), depth=3)
    assert list(pf) == list(range(10))
    pf2 = Prefetcher(iter(range(1000)), depth=2)
    next(pf2)
    pf2.close()


def test_prefetcher_close_reclaims_blocked_producer():
    """Regression: an abandoned iterator with depth=1 leaves the producer
    parked in a full-queue put; close() must still reclaim the thread."""
    pf = Prefetcher(iter(range(10_000)), depth=1)
    next(pf)  # producer now blocked on the full queue
    time.sleep(0.05)
    pf.close()
    assert not pf._thread.is_alive()
    # and closing is idempotent / iteration after close terminates
    pf.close()
    with pytest.raises(StopIteration):
        next(pf)


def test_prefetcher_close_without_consuming():
    before = threading.active_count()
    readers = [Prefetcher(iter(range(100)), depth=1) for _ in range(8)]
    for r in readers:
        r.close()
    deadline = time.monotonic() + 2.0
    while time.monotonic() < deadline and threading.active_count() > before:
        time.sleep(0.01)
    assert all(not r._thread.is_alive() for r in readers)


def test_prefetcher_propagates_errors():
    def gen():
        yield 1
        raise ValueError("boom")

    pf = Prefetcher(gen(), depth=2)
    assert next(pf) == 1
    with pytest.raises(ValueError):
        list(pf)


def test_shard_dus_affinity_roundrobin(mgr):
    shards = make_token_shards(4, 500, vocab_size=32)
    dus = shard_dus(shards, mgr.store, affinities=["cluster:pod0", "cluster:pod1"])
    assert [du.affinity for du in dus] == [
        "cluster:pod0",
        "cluster:pod1",
        "cluster:pod0",
        "cluster:pod1",
    ]


def test_checkpoint_save_restore_roundtrip(mgr):
    pd = mgr.start_pilot_data(
        service_url="sharedfs://cluster:pod0/ck", affinity="cluster:pod0"
    )
    params = {"layer": {"w": np.ones((4, 4), np.float32) * 3}}
    opt = {"step": np.int32(7), "m": {"layer": {"w": np.zeros((4, 4), np.float32)}}}
    ck = Checkpointer(mgr.ctx, run_name="r1")
    du = ck.save(7, params, opt, target=pd)
    assert du.state == DUState.READY
    step, p2, o2 = ck.restore()
    assert step == 7
    np.testing.assert_array_equal(p2["layer"]["w"], params["layer"]["w"])
    assert int(o2["step"]) == 7


def test_checkpoint_bf16_roundtrip_bit_exact(mgr):
    """bf16 leaves (every published config's param dtype) come back from a
    checkpoint DU as bf16 with the same bits — through the Checkpointer and
    through both CU-side readers (serving cold start, trainer restore)."""
    import jax.numpy as jnp
    import ml_dtypes

    from repro.checkpoint import flatten_tree
    from repro.core import FUNCTIONS
    from repro.serving import params_from_input
    from repro.training.trainer import _restore_from_input

    s = mgr.session
    s.start_pilot_data(
        service_url="sharedfs://cluster:pod0/ck-bf16", affinity="cluster:pod0"
    )
    s.start_pilot(resource_url="sim://cluster:pod0:host0", slots=1)
    rng = np.random.default_rng(0)
    params = {
        "embed": {"table": rng.standard_normal((16, 8)).astype(ml_dtypes.bfloat16)},
        "norm": {"scale": rng.standard_normal(8).astype(np.float32)},
    }
    opt = {
        "master": {"embed": {"table": rng.standard_normal((16, 8)).astype(np.float32)}},
        "step": np.int32(3),
    }
    ck = Checkpointer(s, run_name="bf16-rt")
    du = ck.save(3, params, opt)

    def bits(tree):
        return {p: (a.dtype.name, a.shape, a.tobytes()) for p, a in flatten_tree(tree)}

    step, p2, o2 = ck.restore()
    assert step == 3
    assert bits(p2) == bits(params) and bits(o2) == bits(opt)

    @FUNCTIONS.register("read-bf16-ckpt")
    def read(cu_ctx, du_id):
        served = params_from_input(cu_ctx, du_id)
        trained, t_opt = _restore_from_input(cu_ctx, du_id)
        # numeric arrays JAX accepts (a raw-void leaf would raise here)
        assert jnp.asarray(served["embed"]["table"]).dtype == jnp.bfloat16
        return bits(served), bits(trained), bits(t_opt)

    cu = s.submit_cu(executable="read-bf16-ckpt", args=(du.id,), input_data=[du])
    served, trained, t_opt = cu.result(timeout=60)
    assert served == trained == bits(params)
    assert t_opt == bits(opt)


def test_checkpoint_healed_across_pods(healing_mgr):
    """replication_factor=2 + seal → the runtime's ReplicaManager disperses
    the checkpoint across failure domains; no checkpoint-layer code."""
    mgr = healing_mgr
    pd0 = mgr.start_pilot_data(
        service_url="sharedfs://cluster:pod0/ck", affinity="cluster:pod0"
    )
    pd1 = mgr.start_pilot_data(
        service_url="sharedfs://cluster:pod1/ck", affinity="cluster:pod1"
    )
    ck = Checkpointer(mgr.session, run_name="r2", replication_factor=2)
    du = ck.save(1, {"w": np.zeros((2,), np.float32)})
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and len(du.locations) < 2:
        time.sleep(0.02)
    assert set(du.locations) == {pd0.id, pd1.id}
    # pod-local read resolves to the pod-local replica
    step, params, _ = ck.restore(location="cluster:pod1:host0")
    assert step == 1


def test_checkpoint_async(mgr):
    pd = mgr.start_pilot_data(
        service_url="mem://cluster:pod0:host0/ck", affinity="cluster:pod0:host0"
    )
    ck = Checkpointer(mgr.ctx, run_name="r3")
    du = ck.save(2, {"w": np.ones((8,), np.float32)}, target=pd, asynchronous=True)
    ck.wait()
    assert du.state == DUState.READY
    assert ck.latest_step() == 2


def test_checkpoint_async_failure_surfaces_on_wait(mgr):
    """Regression: a failed async commit (quota-starved ingest target) must
    raise from wait(), not vanish in a daemon thread."""
    tiny = mgr.start_pilot_data(
        service_url="mem://cluster:pod0:host0/tiny",
        affinity="cluster:pod0:host0",
        size_quota=16,  # a few-KB checkpoint can never ingest
    )
    ck = Checkpointer(mgr.ctx, run_name="r4")
    ck.save(1, {"w": np.ones((64,), np.float32)}, target=tiny, asynchronous=True)
    with pytest.raises(CheckpointError):
        ck.wait(timeout=10)
    # the failure is consumed: a later wait with nothing pending is clean
    ck.wait(timeout=1)


def test_checkpoint_async_failure_surfaces_on_next_save(mgr):
    tiny = mgr.start_pilot_data(
        service_url="mem://cluster:pod0:host0/tiny2",
        affinity="cluster:pod0:host0",
        size_quota=16,
    )
    good = mgr.start_pilot_data(
        service_url="sharedfs://cluster:pod0/ok", affinity="cluster:pod0"
    )
    ck = Checkpointer(mgr.ctx, run_name="r5")
    ck.save(1, {"w": np.ones((64,), np.float32)}, target=tiny, asynchronous=True)
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and not all(f.done() for f in ck._pending):
        time.sleep(0.02)
    with pytest.raises(CheckpointError):
        ck.save(2, {"w": np.ones((64,), np.float32)}, target=good)
    # error consumed — the next save proceeds normally
    du = ck.save(2, {"w": np.ones((64,), np.float32)}, target=good)
    assert du.state == DUState.READY


def test_checkpoint_wait_raises_on_timeout(mgr):
    pd = mgr.start_pilot_data(
        service_url="sharedfs://cluster:pod0/slow", affinity="cluster:pod0"
    )
    ck = Checkpointer(mgr.ctx, run_name="r6")
    release = threading.Event()
    orig_ingest = mgr.ctx.transfer_service.ingest

    def slow_ingest(du, dst, **kw):
        release.wait(timeout=30)
        return orig_ingest(du, dst, **kw)

    mgr.ctx.transfer_service.ingest = slow_ingest
    try:
        ck.save(1, {"w": np.zeros((4,), np.float32)}, target=pd, asynchronous=True)
        with pytest.raises(CheckpointTimeout):
            ck.wait(timeout=0.2)
        release.set()
        ck.wait(timeout=10)  # the still-pending commit stays waitable
        assert ck.latest_step() == 1
    finally:
        mgr.ctx.transfer_service.ingest = orig_ingest
        release.set()
        ck.close()
