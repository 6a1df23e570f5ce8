"""Serving driver: a pilot fleet cold-starts decode replicas from ONE model
checkpoint DU.

The checkpoint is written once with ``replication_factor=2`` (the runtime's
ReplicaManager disperses it across pods as it seals), and every serve CU
declares it as ``input_data`` — so each replica's weight load goes through
the transfer service, feeds the TierManager's access stats, and after
``tier_promote_after`` loads the DU is PROMOTED into the site's mem-tier
cache: the rest of the fleet warms up from the hot in-memory replica instead
of re-pulling from the shared filesystem (checkpoint-as-DU is how multi-pod
serving fleets warm up without hammering one blob store).

Run:  PYTHONPATH=src python examples/pilot_serve.py
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import Checkpointer
from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.core import FUNCTIONS, Session, make_tpu_fleet_topology
from repro.models import build_model
from repro.serving import DecodeEngine, params_from_input


def main() -> None:
    enable_compile_cache()
    cfg = get_config("gemma3-1b-smoke")  # reduced same-family config
    api = build_model(cfg)
    topo, _ = make_tpu_fleet_topology(pods=2, hosts_per_pod=2)
    with Session(
        topology=topo,
        enable_fault_manager=True,      # heals the ckpt DU to its factor
        tier_cache_bytes=256 * 1024 * 1024,
        tier_promote_after=2,           # promote on the 2nd load at a site
    ) as s:
        # "trained" params, checkpointed ONCE as a replicated DU
        s.start_pilot_data(
            service_url="sharedfs://cluster:pod0/ckpt", affinity="cluster:pod0"
        )
        s.start_pilot_data(
            service_url="sharedfs://cluster:pod1/ckpt", affinity="cluster:pod1"
        )
        params = api.init(jax.random.PRNGKey(0))
        ck = Checkpointer(s, run_name="serve-model", replication_factor=2)
        du = ck.save(0, params)
        deadline = time.time() + 10
        while time.time() < deadline and len(du.locations) < 2:
            time.sleep(0.05)
        print(f"model checkpoint {du.url} healed to {du.locations}")

        # serve executable: weights come from the DU declared as CU input —
        # the tier-cache-eligible cold-start path
        @FUNCTIONS.register("serve_batch")
        def serve_batch(cu_ctx, weights_du, prompt_tokens, new_tokens):
            p = jax.tree.map(jnp.asarray, params_from_input(cu_ctx, weights_du))
            engine = DecodeEngine(api, p, batch=len(prompt_tokens), max_len=64)
            out = engine.generate(jnp.asarray(prompt_tokens, jnp.int32), new_tokens)
            return np.asarray(out).tolist()

        # a fleet: two pilots per pod, one decode replica each
        for pod in (0, 1):
            for host in (0, 1):
                s.start_pilot(
                    resource_url=f"sim://cluster:pod{pod}:host{host}", slots=1
                )
        prompts = [[1, 5, 9, 2], [3, 3, 7, 1]]
        t0 = time.time()
        cus = [
            s.submit_cu(
                executable="serve_batch",
                args=(du.id, prompts, 8),
                input_data=[du],
                affinity=f"cluster:pod{pod}",
            )
            for pod in (0, 1)
            for _ in range(2)
        ]
        outs = [cu.result(timeout=300) for cu in cus]
        for cu, out in zip(cus, outs):
            print(f"{cu.url} on {cu.pilot_id}: generated {out}")
        # every replica must decode identically from its local copy
        assert all(o == outs[0] for o in outs), "replica divergence!"
        tm = s.tier_manager
        stats = tm.access_stats(du.id)
        print(
            f"served {len(cus)} replicas in {time.time()-t0:.1f}s — "
            f"consistent ✓  (ckpt DU accesses: {stats}, "
            f"mem-tier promotions: {tm.promotions_total})"
        )


if __name__ == "__main__":
    main()
