"""End-to-end integration: training driven THROUGH the Pilot-API v2 —
one-shot DAG submission, data-affinity placement, checkpoint-DU chains,
fault recovery, elasticity."""

import threading

import pytest

from repro.configs import get_config
from repro.core import Session, make_tpu_fleet_topology
from repro.training.trainer import PilotTrainer

TINY = dict(
    total_steps=9,
    chunk_steps=3,
    batch=4,
    seq=32,
    peak_lr=3e-3,
    n_shards=2,
    tokens_per_shard=4_000,
)


def tiny_cfg():
    from repro.configs.base import reduced

    return reduced(
        get_config("h2o-danube-1.8b"),
        n_layers=2,
        d_model=32,
        n_heads=2,
        n_kv_heads=1,
        d_ff=64,
        vocab_size=128,
        head_dim=16,
    )


@pytest.fixture()
def sess():
    topo, _ = make_tpu_fleet_topology(pods=2, hosts_per_pod=1)
    with Session(
        topology=topo, enable_heartbeat_monitor=True, heartbeat_timeout_s=0.5
    ) as s:
        yield s


def test_end_to_end_training_improves_loss(sess):
    sess.start_pilot_data(
        service_url="sharedfs://cluster:pod0/scratch", affinity="cluster:pod0"
    )
    p = sess.start_pilot(resource_url="sim://cluster:pod0:host0", slots=1)
    p.wait_active()
    tr = PilotTrainer(tiny_cfg(), sess, run_name="t-e2e", **TINY)
    tr.stage_data(affinities=["cluster:pod0"])
    summary = tr.run()
    assert summary["steps"] == TINY["total_steps"]
    assert summary["improved"], summary
    # the checkpoint chain is a DU chain
    assert len(tr.ckpt_dus) == summary["chunks"] + 1
    params = tr.restore_params()
    assert "embed" in params


def test_bf16_training_resumes_bit_exact_across_chunks(sess):
    """bf16 params (the published dtype) ride the checkpoint-DU chain: every
    chunk restores exactly the state the previous chunk saved."""
    import dataclasses

    sess.start_pilot_data(
        service_url="sharedfs://cluster:pod0/bf16", affinity="cluster:pod0"
    )
    sess.start_pilot(resource_url="sim://cluster:pod0:host0", slots=1)
    cfg = dataclasses.replace(tiny_cfg(), param_dtype="bfloat16")
    tr = PilotTrainer(cfg, sess, run_name="t-bf16", **TINY)
    tr.stage_data(affinities=["cluster:pod0"])
    hist = tr.run()["history"]
    assert len(hist) == 3
    for prev, nxt in zip(hist, hist[1:]):
        assert nxt["restored_digest"] == prev["saved_digest"]
    assert tr.restore_params()["embed"]["table"].dtype.name == "bfloat16"


def test_training_distributes_by_affinity(sess):
    """Shards placed at two sites → chunks run on the co-located pilots."""
    sess.start_pilot_data(
        service_url="sharedfs://cluster:pod0/s0", affinity="cluster:pod0"
    )
    sess.start_pilot_data(
        service_url="sharedfs://cluster:pod1/s1", affinity="cluster:pod1"
    )
    p0 = sess.start_pilot(resource_url="sim://cluster:pod0:host0", slots=1)
    p1 = sess.start_pilot(resource_url="sim://cluster:pod1:host0", slots=1)
    p0.wait_active(), p1.wait_active()
    tr = PilotTrainer(tiny_cfg(), sess, run_name="t-aff", **TINY)
    tr.stage_data(affinities=["cluster:pod0", "cluster:pod1"])
    summary = tr.run()
    assert summary["improved"]
    # chunks alternate shards; both pods' pilots should have participated
    assert len(summary["pilots_used"]) == 2, summary["pilots_used"]


def test_training_survives_pilot_failure(sess):
    """Kill the only active pilot mid-run: the heartbeat monitor requeues
    the chunk; a standby pilot resumes from the checkpoint DU."""
    sess.start_pilot_data(
        service_url="sharedfs://cluster:pod0/s", affinity="cluster:pod0"
    )
    p0 = sess.start_pilot(resource_url="sim://cluster:pod0:host0", slots=1)
    p1 = sess.start_pilot(resource_url="sim://cluster:pod1:host0", slots=1)
    p0.wait_active(), p1.wait_active()
    tr = PilotTrainer(tiny_cfg(), sess, run_name="t-ft", **TINY)
    tr.stage_data(affinities=["cluster:pod0"])

    killer = threading.Timer(1.0, p0.fail)
    killer.start()
    try:
        summary = tr.run(timeout_per_chunk=120.0)
    finally:
        killer.cancel()
    assert summary["steps"] == TINY["total_steps"]
    # at least one chunk must have run on the surviving pilot
    assert p1.id in summary["pilots_used"]


def test_elastic_scale_up_mid_run(sess):
    """A pilot added mid-run picks up later chunks (elastic scaling) —
    even though the WHOLE DAG was submitted before the pilot existed."""
    sess.start_pilot_data(
        service_url="sharedfs://cluster:pod0/s", affinity="cluster:pod0"
    )
    p0 = sess.start_pilot(resource_url="sim://cluster:pod0:host0", slots=1)
    p0.wait_active()
    tr = PilotTrainer(
        tiny_cfg(),
        sess,
        run_name="t-elastic",
        total_steps=8,
        chunk_steps=2,
        batch=2,
        seq=32,
        n_shards=1,
        tokens_per_shard=4_000,
    )
    tr.stage_data(affinities=None)

    added = {}

    def add_pilot():
        p_new = sess.start_pilot(resource_url="sim://cluster:pod0:host0", slots=1)
        added["pilot"] = p_new
        # freeze the original so the new pilot must take over
        p0.cancel()

    threading.Timer(1.0, add_pilot).start()
    summary = tr.run(timeout_per_chunk=120.0)
    assert summary["steps"] == 8
    assert added["pilot"].id in summary["pilots_used"]
