"""End-to-end driver: train a ~100M-param LM for a few hundred steps THROUGH
the Pilot-Data abstractions.

The run is ONE declaratively-submitted CU/DU DAG on the Session API: chunked
shard DUs (data), a checkpoint-DU chain (model state) wired future-to-future,
train-chunk CUs late-bound to pilots co-located with their inputs.  Every
checkpoint DU carries ``replication_factor=2`` — the runtime's ReplicaManager
disperses it across pods as it seals, so kill -9 any pilot mid-run and the
chunk replays from a surviving checkpoint replica (no trainer-side recovery
code).

Run (full, ~100M params, few hundred steps — takes a while on CPU):
  PYTHONPATH=src python examples/pilot_train.py --preset full
Run (demo, ~4M params, 30 steps, ~2 min):
  PYTHONPATH=src python examples/pilot_train.py
"""

import argparse
import dataclasses
import time

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.configs.base import reduced
from repro.core import Session, make_tpu_fleet_topology
from repro.training.trainer import PilotTrainer

PRESETS = {
    # ~4M params — quick demo
    "demo": dict(
        model=dict(
            n_layers=4, d_model=128, n_heads=4, n_kv_heads=2, d_ff=512,
            vocab_size=2048, head_dim=32,
        ),
        total_steps=30, chunk_steps=10, batch=8, seq=128,
        tokens_per_shard=200_000,
    ),
    # ~100M params — the assignment's end-to-end driver scale
    "full": dict(
        model=dict(
            n_layers=12, d_model=768, n_heads=12, n_kv_heads=4, d_ff=2048,
            vocab_size=32000, head_dim=64,
        ),
        total_steps=300, chunk_steps=25, batch=8, seq=256,
        tokens_per_shard=2_000_000,
    ),
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", choices=sorted(PRESETS), default="demo")
    args = ap.parse_args()
    enable_compile_cache()
    preset = PRESETS[args.preset]

    cfg = reduced(get_config("h2o-danube-1.8b"), **preset["model"])
    cfg = dataclasses.replace(cfg, name=f"pilot-train-{args.preset}")
    print(f"model: {cfg.name} — {cfg.param_count()/1e6:.1f}M params")

    topo, _ = make_tpu_fleet_topology(pods=2, hosts_per_pod=1)
    with Session(
        topology=topo, enable_fault_manager=True, heartbeat_timeout_s=2.0
    ) as s:
        # data lives on each pod's shared FS; pilots on both pods
        s.start_pilot_data(
            service_url="sharedfs://cluster:pod0/scratch", affinity="cluster:pod0"
        )
        s.start_pilot_data(
            service_url="sharedfs://cluster:pod1/scratch", affinity="cluster:pod1"
        )
        s.start_pilot(resource_url="sim://cluster:pod0:host0", slots=1)
        s.start_pilot(resource_url="sim://cluster:pod1:host0", slots=1)

        tr = PilotTrainer(
            cfg,
            s,
            total_steps=preset["total_steps"],
            chunk_steps=preset["chunk_steps"],
            batch=preset["batch"],
            seq=preset["seq"],
            peak_lr=3e-3,
            n_shards=2,
            tokens_per_shard=preset["tokens_per_shard"],
            run_name=cfg.name,
            ckpt_replication=2,
        )
        tr.stage_data(affinities=["cluster:pod0", "cluster:pod1"])
        t0 = time.time()
        summary = tr.run(timeout_per_chunk=3600)
        dt = time.time() - t0
        print(f"\ntrained {summary['steps']} steps in {dt:.0f}s "
              f"({summary['chunks']} chunks on pilots {summary['pilots_used']})")
        print(f"loss: {summary['first_loss']:.3f} → {summary['final_loss']:.3f} "
              f"(improved={summary['improved']})")
        for h in summary["history"]:
            print(f"  chunk {h['chunk']:3d} steps={h['steps']} pilot={h['pilot']} "
                  f"loss_tail={h['losses'][-1]:.3f}")
        last = tr.ckpt_dus[-1]
        params = tr.restore_params()
        print(f"restored params from {last.url} "
              f"(replicas: {last.locations}): {len(params)} top-level entries")


if __name__ == "__main__":
    main()
