"""repro.tracing: the program's spans and counters — the CU lifecycle
against CUTimings, parents per thread, compile attribution, the bounded
ring, the profiler's host plane, and a checkpoint save."""

import threading

import numpy as np
import pytest

from repro import tracing
from repro.checkpoint import Checkpointer
from repro.core import (
    DataUnitDescription,
    FUNCTIONS,
    Session,
    make_tpu_fleet_topology,
)


def _by_id(records):
    return {r.id: r for r in records}


def _session(**kwargs):
    topo, hosts = make_tpu_fleet_topology(pods=1, hosts_per_pod=2)
    s = Session(topology=topo, **kwargs)
    s.start_pilot_data(
        service_url="sharedfs://cluster:pod0/trace", affinity="cluster:pod0"
    )
    return s, hosts


@pytest.mark.parametrize("mode", ["sync", "async"])
def test_cu_lifecycle_spans_match_timings(mode):
    exe = f"trace-read-{mode}"

    @FUNCTIONS.register(exe)
    def read(cu_ctx, du_id):
        data = cu_ctx.read_input(du_id, "part.bin")
        cu_ctx.write_output("n.txt", str(len(data)).encode())
        return len(data)

    s, hosts = _session(scheduler_mode=mode)
    with s:
        s.start_pilot(resource_url=f"sim://{hosts[0]}", slots=1)
        du = s.submit_du(name="in", files={"part.bin": b"x" * 5000})
        fut = s.submit_cu(
            executable=exe,
            args=(du.id,),
            input_data=[du],
            output_data=[DataUnitDescription(name="out")],
        )
        assert fut.result(timeout=60) == 5000
    cu_id = fut.cu.id
    mine = [r for r in tracing.spans() if r.cu == cu_id]
    names = {r.name: r for r in mine}
    for name in ("cu.queue", "cu.stage_in", "transfer.stage_in", "cu.run",
                 "du.read", "cu.stage_out"):
        assert name in names, name
    t = fut.timings

    def bounds(name):
        return names[name].start, names[name].end

    assert bounds("cu.queue") == (t.submitted, t.stage_start)
    assert bounds("cu.stage_in") == (t.stage_start, t.stage_end)
    assert bounds("cu.run") == (t.run_start, t.run_end)
    assert names["cu.stage_out"].start >= t.run_end
    assert names["transfer.stage_in"].parent == names["cu.stage_in"].id
    assert names["transfer.stage_in"].attrs["linked"] is True  # sharedfs on the pod
    read_rec = names["du.read"]
    assert read_rec.parent == names["cu.run"].id
    assert read_rec.attrs["bytes"] == 5000 and read_rec.attrs["via"] == "link"
    assert read_rec.attrs["chunks"] >= 1


def test_parents_nest_per_agent_thread():
    """Two CUs on two agent threads, inside their spans at the same time:
    each body's span has its own CU's ``cu.run`` as parent."""
    both = threading.Barrier(2, timeout=30)

    @FUNCTIONS.register("trace-overlap")
    def body(cu_ctx):
        with tracing.span("test.body"):
            both.wait()
            with tracing.span("test.inner"):
                both.wait()
        return cu_ctx.cu.id

    s, hosts = _session()
    with s:
        for h in hosts:
            s.start_pilot(resource_url=f"sim://{h}", slots=1)
        futs = [s.submit_cu(executable="trace-overlap") for _ in range(2)]
        ids = [f.result(timeout=60) for f in futs]
    recs = _by_id(tracing.spans())
    for cu_id in ids:
        mine = {r.name: r for r in recs.values() if r.cu == cu_id}
        assert mine["test.body"].parent == mine["cu.run"].id
        assert mine["test.inner"].parent == mine["test.body"].id
    bodies = [r for r in recs.values() if r.name == "test.body" and r.cu in ids]
    assert len(bodies) == 2
    # they overlapped in time, on different threads
    a, b = bodies
    assert a.start < b.end and b.start < a.end


def test_threads_keep_their_own_stacks():
    ready = threading.Barrier(2, timeout=10)
    out = {}

    def work(tag):
        with tracing.span(f"test.outer.{tag}") as outer:
            ready.wait()
            with tracing.span(f"test.inner.{tag}") as inner:
                ready.wait()
        out[tag] = (outer, inner)

    threads = [threading.Thread(target=work, args=(t,)) for t in "ab"]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    for outer, inner in out.values():
        assert inner.parent == outer.id
        assert outer.parent is None
        assert inner.cu is None


def test_cu_is_inherited_or_explicit():
    with tracing.span("test.request", cu="cu-7") as outer:
        with tracing.span("test.child") as child:
            tracing.record("test.interval", child.start, child.start)
        with tracing.span("test.other", cu="cu-8") as other:
            pass
    recs = [r for r in tracing.spans() if r.name == "test.interval"]
    assert recs[-1].cu == "cu-7" and recs[-1].parent == child.id
    assert child.cu == "cu-7" and child.parent == outer.id
    assert other.cu == "cu-8"


def test_new_jit_is_attributed_to_the_open_span():
    import jax
    import jax.numpy as jnp

    before = tracing.counters().get("jit.trace", 0.0)

    def fresh(x):  # a new function: nothing cached for it
        return jnp.sin(x) * 3.0 + 1.0

    x = jnp.ones(4)
    with tracing.span("test.compile") as sp:
        jax.jit(fresh)(x).block_until_ready()
    jit = [
        r for r in tracing.spans() if r.parent == sp.id and r.name.startswith("jit.")
    ]
    traces = [r for r in jit if r.name == "jit.trace"]
    assert "fresh" in {r.attrs.get("fun_name") for r in traces}
    assert {"jit.lower", "jit.compile"} <= {r.name for r in jit}
    assert all(sp.start <= r.start and r.end <= sp.end + 1e-3 for r in jit)
    assert tracing.counters()["jit.trace"] > before


def test_ring_drops_oldest_and_counts():
    tracing.count("test.survivor", 3)
    dropped0 = tracing.counters().get("tracing.dropped", 0)
    held0 = len(tracing.spans())
    n = tracing.CAPACITY + 10
    for i in range(n):
        tracing.record("test.flood", 0.0, 0.0, i=i)
    recs = tracing.spans()
    assert len(recs) == tracing.CAPACITY
    flood = [r.attrs["i"] for r in recs if r.name == "test.flood"]
    assert flood[-1] == n - 1
    assert flood[0] >= 10  # the oldest went first
    assert tracing.counters()["tracing.dropped"] - dropped0 >= held0 + 10
    assert tracing.counters()["test.survivor"] >= 3


def test_span_is_on_the_profilers_host_plane(tmp_path):
    import glob

    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing.span("test.profiled", cu="cu-trace"):
            jnp.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    data = ProfileData.from_file(path)
    names = {
        ev.name
        for plane in data.planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for ev in line.events
    }
    assert any(n.startswith(tracing.PREFIX + "test.profiled") for n in names), names


def _params():
    rng = np.random.default_rng(0)
    return {"a": {"w": rng.standard_normal((8, 8)).astype(np.float32)},
            "b": np.arange(5, dtype=np.int32)}


def test_checkpoint_save_spans():
    s, _ = _session()
    with s:
        du = Checkpointer(s, run_name="trace-ck").save(3, _params())
    recs = tracing.spans()
    (save,) = [r for r in recs if r.name == "ckpt.save" and r.attrs.get("du") == du.id]
    under = [r for r in recs if r.parent == save.id]
    names = [r.name for r in under]
    assert names.count("ckpt.encode") == 2  # one per leaf
    assert {"du.create", "du.ingest", "du.seal"} <= set(names)
    (create,) = [r for r in under if r.name == "du.create"]
    assert create.attrs["du"] == du.id and create.attrs["chunks"] == du.n_chunks


def test_async_checkpoint_commit_on_its_own_thread():
    s, _ = _session()
    with s:
        ck = Checkpointer(s, run_name="trace-ck-async")
        du = ck.save(4, _params(), asynchronous=True)
        ck.wait()
        ck.close()
    recs = tracing.spans()
    (commit,) = [r for r in recs if r.name == "ckpt.commit" and r.attrs["du"] == du.id]
    assert commit.parent is None  # not under the caller's ckpt.save
    under = {r.name for r in recs if r.parent == commit.id}
    assert {"du.ingest", "du.seal"} <= under
