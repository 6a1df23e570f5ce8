"""PilotTrainer: training as a Pilot-Data dataflow.

The run is expressed EXACTLY in the paper's nouns (§4.3.2, Fig. 5):

  * the corpus is partitioned into *chunked* shard DUs (partitioned data)
    placed by affinity across Pilot-Data;
  * model state moves through the run as a chain of immutable checkpoint
    DUs carrying a ``replication_factor`` — healing after a pilot loss is
    the runtime's ReplicaManager, not trainer code;
  * each training chunk (N optimizer steps) is a Compute-Unit with
    ``input_data = [shard_du, ckpt_{i-1}]`` and ``output_data = [ckpt_i]``;
  * the Compute-Data Service late-binds each chunk to a pilot co-located
    with its inputs (compute-to-data), re-queues it if a pilot dies
    (restart from ckpt_{i-1} — checkpoint/restart for free), and new pilots
    added mid-run simply start pulling chunks (elastic scaling).

The WHOLE chunk DAG is submitted in one shot through the Session API:
chunk i+1 names chunk i's output DUFuture as an input, the dependency
tracker parks it ``Waiting`` until ckpt_i seals, and under the async
scheduler the released/waiting prefetch hooks overlap chunk i+1's shard
stage-in with chunk i's compute.  The chunk executable holds the jitted
train_step; all cross-chunk state is in DUs, so a chunk can run anywhere —
which is the whole point.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

from ..checkpoint import (
    checkpoint_files,
    decode_array,
    tree_digest,
    unflatten_tree,
)
from ..configs.base import ModelConfig
from ..core import DataUnitDescription, FUNCTIONS
from ..core.futures import CUFuture, DUFuture
from ..data import (
    Prefetcher,
    SHARD_CHUNK_BYTES,
    ShardReader,
    StreamingShardReader,
    make_token_shards,
    stage_shard_dus,
)
from ..models import build_model
from ..optim import init_adamw
from .train_step import make_train_step


def _restore_from_input(cu_ctx, ckpt_du: str) -> Tuple[Any, Any]:
    """(params, opt_state) from a checkpoint DU staged as a CU input."""
    items_p, items_o = {}, {}
    for rel in cu_ctx.input_manifest(ckpt_du):
        if rel.startswith("params/") and rel.endswith(".npy"):
            items_p[rel[7:-4]] = decode_array(cu_ctx.read_input(ckpt_du, rel))
        elif rel.startswith("opt/") and rel.endswith(".npy"):
            items_o[rel[4:-4]] = decode_array(cu_ctx.read_input(ckpt_du, rel))
    return unflatten_tree(items_p), unflatten_tree(items_o)


class PilotTrainer:
    """Drives a training run as one declaratively-submitted CU/DU DAG.

    ``runtime`` is a :class:`~repro.core.session.Session` or anything that
    exposes one (``PilotManager.session``).  ``ckpt_replication`` is the
    replication factor stamped on every checkpoint DU — with the fault
    manager enabled, the runtime heals each sealed checkpoint to that many
    failure domains, so a mid-run pilot kill costs one chunk replay, not
    the run.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        runtime: Any,
        total_steps: int = 20,
        chunk_steps: int = 5,
        batch: int = 4,
        seq: int = 64,
        peak_lr: float = 1e-3,
        n_shards: int = 2,
        tokens_per_shard: int = 50_000,
        seed: int = 0,
        run_name: str = "pilot-train",
        ckpt_replication: int = 1,
        shard_chunk_bytes: int = SHARD_CHUNK_BYTES,
    ):
        self.cfg = cfg
        # a PilotManager exposes its v2 facade as .session; a Session IS one
        self.session = getattr(runtime, "session", runtime)
        self.total_steps = total_steps
        self.chunk_steps = chunk_steps
        self.batch = batch
        self.seq = seq
        self.peak_lr = peak_lr
        self.n_shards = n_shards
        self.tokens_per_shard = tokens_per_shard
        self.seed = seed
        self.run_name = run_name
        self.ckpt_replication = ckpt_replication
        self.shard_chunk_bytes = shard_chunk_bytes
        self.api = build_model(cfg)
        self.shard_dus: List[DUFuture] = []
        self.ckpt_dus: List[DUFuture] = []
        self.history: List[Dict] = []
        self._register_executable()

    # ------------------------------------------------------------ plumbing
    def _register_executable(self) -> None:
        api = self.api
        me = self

        @functools.lru_cache(maxsize=4)
        def jitted_step(mb: int):
            import jax

            return jax.jit(
                make_train_step(
                    api,
                    peak_lr=me.peak_lr,
                    warmup_steps=max(2, me.total_steps // 10),
                    total_steps=me.total_steps,
                )
            )

        def train_chunk(cu_ctx, shard_du, ckpt_du, start_step, n_steps, batch, seq):
            params, opt_state = _restore_from_input(cu_ctx, ckpt_du)
            restored_digest = tree_digest({"params": params, "opt": opt_state})
            # --- data from the co-located shard DU ---
            manifest = cu_ctx.input_manifest(shard_du)
            if any(rel.endswith(".bin") for rel in manifest):
                # chunk-streamable raw shard: consume the canonical byte
                # stream chunk-by-chunk (prefix batches start before the
                # whole shard is local)
                reader = StreamingShardReader(cu_ctx, shard_du)
            else:
                reader = ShardReader.from_cu_context(cu_ctx, shard_du, seed=me.seed)
            batches = Prefetcher(
                reader.batches(batch, seq, start_step=start_step), depth=2
            )
            step_fn = jitted_step(1)
            losses = []
            try:
                for _, b in zip(range(n_steps), batches):
                    params, opt_state, metrics = step_fn(params, opt_state, b)
                    losses.append(float(metrics["loss"]))
            finally:
                batches.close()
            # --- emit the next checkpoint DU ---
            saved_digest = tree_digest({"params": params, "opt": opt_state})
            for rel, data in checkpoint_files(
                start_step + n_steps, me.run_name, params, opt_state
            ).items():
                cu_ctx.write_output(rel, data)
            return {
                "losses": losses,
                "final_loss": losses[-1] if losses else None,
                # equal digests across a chunk boundary: the next chunk
                # resumed from exactly the state this one saved
                "restored_digest": restored_digest,
                "saved_digest": saved_digest,
            }

        FUNCTIONS.register(f"train_chunk:{self.run_name}", train_chunk)

    # ---------------------------------------------------------------- setup
    def stage_data(self, affinities: Optional[List[Optional[str]]] = None) -> None:
        """Create + place the shard DUs (partitioned-data pattern): raw
        chunk-streamable format, chunked manifests, affinity round-robin."""
        shards = make_token_shards(
            self.n_shards,
            self.tokens_per_shard,
            self.cfg.vocab_size,
            seed=self.seed,
            fmt="raw",
        )
        self.shard_dus = stage_shard_dus(
            self.session,
            shards,
            name=self.run_name,
            affinities=affinities,
            chunk_size=self.shard_chunk_bytes,
        )

    def initial_checkpoint(self) -> DUFuture:
        """ckpt_0 from fresh init (also a DU, so chunk 0 is uniform)."""
        import jax

        params = self.api.init(jax.random.PRNGKey(self.seed))
        opt_state = init_adamw(params)
        fut = self.session.submit_du(
            name=f"{self.run_name}.ckpt{0:08d}",
            files=checkpoint_files(0, self.run_name, params, opt_state),
            replication_factor=self.ckpt_replication,
        )
        self.session.store.hset(f"ckpt:{self.run_name}", f"{0:08d}", fut.id)
        self._drop_buffer(fut)
        self.ckpt_dus.append(fut)
        return fut

    def _drop_buffer(self, ckpt: DUFuture) -> None:
        """A checkpoint held by a Pilot-Data needs no in-process staging
        copy too: it would double the host memory of every checkpoint in
        the chain."""
        du = self.session.ctx.lookup(ckpt.id)
        if du.locations:
            du.drop_local_buffer()

    # ----------------------------------------------------------------- run
    def submit_dag(self) -> List[Tuple[int, int, int, CUFuture]]:
        """Submit the ENTIRE chunk chain upfront — one shot, no user-side
        waits between chunks.  Each chunk's checkpoint input is the
        previous chunk's output DUFuture; the runtime's DU-readiness gate
        sequences the chain and (async mode) pipelines the stage-ins.

        Returns ``[(chunk_idx, start_step, n_steps, cu_future), ...]``."""
        if not self.shard_dus:
            self.stage_data()
        ckpt = self.ckpt_dus[-1] if self.ckpt_dus else self.initial_checkpoint()
        chunks = []
        step = 0
        chunk_idx = 0
        while step < self.total_steps:
            n = min(self.chunk_steps, self.total_steps - step)
            shard = self.shard_dus[chunk_idx % len(self.shard_dus)]
            # NOTE: no hard affinity constraint — data locality is a SOFT
            # preference expressed through the CDS's input-data scoring
            # (§6.1); a hard constraint would pin chunks to a site even
            # after its pilots die, defeating failover.
            cu = self.session.submit_cu(
                executable=f"train_chunk:{self.run_name}",
                args=(shard.id, ckpt.id, step, n, self.batch, self.seq),
                input_data=[shard, ckpt],
                output_data=[
                    DataUnitDescription(
                        name=f"{self.run_name}.ckpt{step + n:08d}",
                        replication_factor=self.ckpt_replication,
                    )
                ],
                max_retries=4,
            )
            chunks.append((chunk_idx, step, n, cu))
            ckpt = cu.output
            step += n
            chunk_idx += 1
        return chunks

    def run(self, timeout_per_chunk: float = 300.0) -> Dict[str, Any]:
        """Submit the one-shot DAG, then collect; returns the loss summary."""
        chunks = self.submit_dag()
        for chunk_idx, step, n, cu in chunks:
            res = cu.result(timeout=timeout_per_chunk)
            self.history.append(
                {
                    "chunk": chunk_idx,
                    "steps": (step, step + n),
                    "pilot": cu.pilot_id,
                    "losses": res["losses"],
                    "restored_digest": res["restored_digest"],
                    "saved_digest": res["saved_digest"],
                    "t_s_sim": cu.timings.sim_stage_s,
                }
            )
            self._drop_buffer(cu.output)
            self.ckpt_dus.append(cu.output)
            self.session.store.hset(
                f"ckpt:{self.run_name}", f"{step + n:08d}", cu.output.id
            )
        first = self.history[0]["losses"][0]
        last = self.history[-1]["losses"][-1]
        return {
            "steps": self.total_steps,
            "chunks": len(chunks),
            "first_loss": first,
            "final_loss": last,
            "improved": last < first,
            "pilots_used": sorted({h["pilot"] for h in self.history}),
            "history": self.history,
        }

    def restore_params(self) -> Any:
        """Load params from the latest checkpoint DU (resharding restore)."""
        from ..checkpoint import load_checkpoint_du

        ctx = self.session.ctx
        du = ctx.lookup(self.ckpt_dus[-1].id)
        _, params, _ = load_checkpoint_du(ctx, du)
        return params
