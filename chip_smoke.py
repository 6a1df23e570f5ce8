"""Smoke run of the main path on one TPU chip, at published widths.

    python3 chip_smoke.py

Drives the system as its users do — a ``Session`` with Pilot-Data and
pilots, checkpoint DUs, CUs on agent threads — in four phases:

  1. device  — the first JAX device must be a TPU, else exit 1;
  2. kernels — the four Pallas kernels, compiled (``interpret=False``) at
               the widths of ``repro.kernels.cases``, each against its
               fp32 reference within the tolerance of ``tests/test_kernels.py``;
  3. serve   — gemma3-1b (published config, bf16) saved once as a
               checkpoint DU on a ``sharedfs://`` Pilot-Data, then two serve
               CUs on two pilots each cold-start a ``DecodeEngine`` from it;
               the replicas must agree, and a teacher-forced forward pass
               must agree with their greedy tokens;
  4. train   — mamba2-370m (published config, bf16 + fp32 AdamW state)
               through ``PilotTrainer``: 2 chunks × 3 steps at batch 4 ×
               seq 2048; losses finite, the first near ln(vocab), and chunk 1
               resumes from exactly the state chunk 0 saved.

Every input comes from ``--seed``.  Lines before the last are informational
(wall and compile times, peak device memory, counts), not benchmark
metrics.  Any failed check raises, so the script exits non-zero and prints
no verdict; the last line, printed only when every phase passed, is
``{"ok": true, "device": {...}}``.  It needs ``src/repro`` next to it.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import os
import resource
import sys
import tempfile
import time
import traceback
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

#: teacher-forced greedy agreement the serve phase requires (see
#: ``check_teacher_forced``)
MIN_AGREEMENT = 0.9
#: a disagreement must be a near-tie: the reference logit of the engine's
#: token within this many bf16 ulps (at the top logit's magnitude) of the
#: reference maximum
NEAR_TIE_ULPS = 8
#: decode-step logits vs the causal pass, as a max error normalized by the
#: largest |logit|.  At gemma3-1b widths in bf16 the two differ by 7e-3 at
#: 6 layers and 1.1e-2 at 18 (XLA:CPU), and by 1.4e-2 at all 26 on a TPU
#: v5e; a cache read one position off gives 0.5 (XLA:CPU, 6 layers)
LOGIT_TOL = 0.05
#: |first loss - ln(vocab)| allowed in the train phase (see ``train_phase``)
LOSS_BAND = 1.0


class PhaseClock:
    """Wall time and backend compile time of one phase."""

    def __init__(self):
        self.compile_s = 0.0
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def run(self, name, fn, *args, **kwargs):
        import jax

        c0, t0 = self.compile_s, time.perf_counter()
        out = fn(*args, **kwargs)
        wall = time.perf_counter() - t0
        stats = jax.devices()[0].memory_stats() or {}
        host_kib = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        with open("/proc/self/statm") as f:
            host_rss = int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
        print(
            f"phase {name}: wall_s={wall:.1f} compile_s={self.compile_s - c0:.1f} "
            f"peak_bytes_in_use={stats.get('peak_bytes_in_use', 'n/a')} "
            f"host_rss_bytes={host_rss} host_max_rss_bytes={host_kib * 1024}",
            flush=True,
        )
        return out


def require(cond, what):
    if not cond:
        raise AssertionError(what)


# ------------------------------------------------------------------ kernels
def kernel_phase(*, seed: int) -> None:
    import jax

    from repro.kernels.cases import KERNEL_CASES, max_normalized_error

    off = []
    for i, case in enumerate(KERNEL_CASES):
        args = jax.jit(case.make_inputs)(jax.random.PRNGKey(seed + i))
        err = max_normalized_error(case.kernel(*args), case.reference(*args))
        print(f"kernel {case.name}: max_normalized_error={err:.3e} tol={case.tol:.0e}")
        if not err < case.tol:
            off.append(case.name)
    require(not off, f"kernels off their references: {off}")


# -------------------------------------------------------------------- serve
def check_teacher_forced(api, params, prompts, generated, max_len) -> None:
    """Greedy tokens vs a teacher-forced ``api.forward`` over prompt plus
    continuation.

    The engine and the forward pass compute the same logits in a different
    order (one query against a KV cache vs one causal pass) and round them
    to bf16 at different points, so an exact tie-break between two logits a
    few bf16 ulps apart can flip.  Hence: at least ``MIN_AGREEMENT`` of the
    positions agree exactly, and any that does not is a near-tie in the
    reference.  A random-weight model mostly repeats its input token (the
    tied embedding's self-logit dominates), which argmax agreement alone
    would not tell from a broken cache; so the decode step's logits are also
    compared, position by position through a KV cache as ``DecodeEngine``
    runs it, with the causal pass's, within ``LOGIT_TOL``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.cases import max_normalized_error

    full = jnp.asarray(np.concatenate([prompts, generated[:, :-1]], axis=1))
    fwd = jax.jit(lambda p, t: api.forward(p, t)[0])(params, full)  # [B, T, V]
    p_len = prompts.shape[1]
    ref = np.asarray(fwd[:, p_len - 1 :].astype(jnp.float32))
    top = ref.max(axis=-1)
    picked = np.take_along_axis(ref, generated[..., None], axis=-1)[..., 0]
    agree = float((ref.argmax(axis=-1) == generated).mean())
    ulp = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(top), 1e-30))) - 7)
    worst = float(((top - picked) / ulp).max())
    repeats = float((generated == prompts[:, -1:]).mean())

    step = jax.jit(api.decode_step)
    cache = api.init_cache(full.shape[0], max_len)
    err = 0.0
    for i in range(full.shape[1]):
        logits, cache = step(params, cache, full[:, i : i + 1], jnp.int32(i))
        err = max(err, max_normalized_error(logits[:, 0], fwd[:, i]))
    print(
        f"serve teacher-forced: agreement={agree:.4f} over {generated.size} "
        f"tokens, worst gap={worst:.1f} bf16 ulps, repeats of the last prompt "
        f"token={repeats:.4f}; decode-vs-forward logits "
        f"max_normalized_error={err:.3e} tol={LOGIT_TOL}"
    )
    require(agree >= MIN_AGREEMENT, f"teacher-forced agreement {agree}")
    require(worst <= NEAR_TIE_ULPS, f"a disagreement is {worst} ulps off")
    require(err < LOGIT_TOL, f"decode logits off the forward pass by {err}")


def serve_phase(
    cfg,
    *,
    seed: int,
    batch: int = 8,
    prompt_len: int = 32,
    new_tokens: int = 32,
    max_len: int = 1024,
) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.checkpoint import Checkpointer
    from repro.core import FUNCTIONS, CUState, Session, make_tpu_fleet_topology
    from repro.models import build_model
    from repro.serving import DecodeEngine, params_from_input

    api = build_model(cfg)
    name = f"chip-smoke-serve:{cfg.name}"

    def serve(cu_ctx, weights_du, prompts, n_new):
        params = jax.device_put(params_from_input(cu_ctx, weights_du))
        engine = DecodeEngine(api, params, batch=len(prompts), max_len=max_len)
        out = engine.generate(jnp.asarray(prompts, jnp.int32), n_new)
        return np.asarray(out)

    FUNCTIONS.register(name, serve)
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, cfg.vocab_size, (batch, prompt_len), dtype=np.int32)
    topo, _ = make_tpu_fleet_topology(pods=1, hosts_per_pod=2)
    with Session(topology=topo) as s:
        s.start_pilot_data(
            service_url="sharedfs://cluster:pod0/chip-smoke", affinity="cluster:pod0"
        )
        params = jax.jit(api.init)(jax.random.PRNGKey(seed))
        n_params = sum(x.size for x in jax.tree.leaves(params))
        du = Checkpointer(s, run_name=name).save(0, params)
        print(f"serve checkpoint: {cfg.name} {n_params} params, {du.size} bytes")
        pilots = [
            s.start_pilot(resource_url=f"sim://cluster:pod0:host{h}", slots=1)
            for h in range(2)
        ]
        cus = [
            s.submit_cu(
                executable=name,
                args=(du.id, prompts, new_tokens),
                input_data=[du],
                pilot=p,
                max_retries=0,
            )
            for p in pilots
        ]
        outs = [cu.result(timeout=900) for cu in cus]
        require(all(cu.state == CUState.DONE for cu in cus), "a serve CU not Done")
        require(
            len({cu.pilot_id for cu in cus}) == 2, "replicas shared a pilot"
        )
        for out in outs:
            require(out.shape == (batch, new_tokens), f"tokens shape {out.shape}")
        require(all((o == outs[0]).all() for o in outs), "replicas disagree")
        print(
            f"serve: {len(cus)} replicas x {batch} sequences x "
            f"({prompt_len} prompt + {new_tokens} new) tokens, replicas identical"
        )
        check_teacher_forced(api, params, prompts, outs[0], max_len)


# -------------------------------------------------------------------- train
def train_phase(
    cfg,
    *,
    seed: int,
    chunks: int = 2,
    chunk_steps: int = 3,
    batch: int = 4,
    seq: int = 2048,
) -> None:
    """The first loss of a freshly initialised LM is close to ln(vocab):
    the final RMSNorm makes each hidden unit ~unit variance and the tied
    embedding is N(0, 0.02²), so logits are ~N(0, (0.02·√d)²) —
    0.64 at d=1024 — and the expected loss is ln(V) + 0.64²/2 ≈ ln(V) + 0.2.
    ``LOSS_BAND`` leaves room for the data's Zipf skew and the sampling."""
    import numpy as np

    from repro.core import Session, make_tpu_fleet_topology
    from repro.training.trainer import PilotTrainer

    total = chunks * chunk_steps
    topo, _ = make_tpu_fleet_topology(pods=1, hosts_per_pod=1)
    with Session(topology=topo) as s:
        # a site-shared Pilot-Data is a logical link for the pilot, so
        # staging a 5 GB checkpoint (370M params with fp32 AdamW state) into
        # the pilot's sandbox costs no second copy in host memory
        s.start_pilot_data(
            service_url="sharedfs://cluster:pod0/chip-smoke", affinity="cluster:pod0"
        )
        s.start_pilot(resource_url="sim://cluster:pod0:host0", slots=1)
        tr = PilotTrainer(
            cfg,
            s,
            total_steps=total,
            chunk_steps=chunk_steps,
            batch=batch,
            seq=seq,
            n_shards=chunks,
            tokens_per_shard=total * batch * (seq + 1),
            seed=seed,
            run_name=f"chip-smoke-train:{cfg.name}",
            ckpt_replication=1,
        )
        summary = tr.run(timeout_per_chunk=900)
    hist = summary["history"]
    losses = [x for h in hist for x in h["losses"]]
    print(
        f"train: {summary['steps']} steps in {summary['chunks']} chunks, "
        f"{summary['steps'] * batch * seq} tokens, losses {losses}"
    )
    require(len(losses) == total, f"{len(losses)} losses for {total} steps")
    require(all(np.isfinite(losses)), "a loss is not finite")
    ln_v = math.log(cfg.vocab_size)
    require(
        abs(losses[0] - ln_v) <= LOSS_BAND,
        f"first loss {losses[0]} not within {LOSS_BAND} of ln(V)={ln_v:.3f}",
    )
    for prev, nxt in zip(hist, hist[1:]):
        require(
            nxt["restored_digest"] == prev["saved_digest"],
            f"chunk {nxt['chunk']} restored another state than chunk "
            f"{prev['chunk']} saved",
        )
    print(
        f"train: first loss {losses[0]:.4f} (ln V = {ln_v:.4f}); every chunk "
        f"restored the previous chunk's checkpoint bit for bit"
    )


# --------------------------------------------------------------------- main
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repo sources at {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as storage:
        # file-backed Pilot-Data lives here and goes with the run
        os.environ["REPRO_STORAGE_ROOT"] = storage
        return run(args.seed)


def run(seed: int) -> int:
    import jax

    from repro.compile_cache import enable_compile_cache
    from repro.configs import get_config

    devices = jax.devices()
    dev = devices[0]
    print(
        f"device: platform={dev.platform} kind={dev.device_kind} count={len(devices)}",
        flush=True,
    )
    if dev.platform != "tpu":
        print("chip_smoke: no TPU found", file=sys.stderr)
        return 1
    print(f"compile cache: {enable_compile_cache()}")

    clock = PhaseClock()
    phases = [
        ("kernels", kernel_phase, ()),
        ("serve", serve_phase, (get_config("gemma3-1b"),)),
        ("train", train_phase, (get_config("mamba2-370m"),)),
    ]
    failed = []
    for name, fn, args in phases:
        # a failed phase is reported and the next one still runs, so one
        # chip run shows every fault; any failure withholds the verdict
        try:
            clock.run(name, fn, *args, seed=seed)
        except Exception:  # noqa: BLE001 — reported, exits non-zero below
            traceback.print_exc()
            print(f"phase {name}: FAILED", flush=True)
            failed.append(name)
        gc.collect()  # drop the phase's device arrays before the next
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    verdict = {
        "ok": True,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(devices),
        },
    }
    print(json.dumps(verdict))
    return 0


if __name__ == "__main__":
    sys.exit(main())
