"""Checkpointing as Data-Units.

A checkpoint is an immutable DU whose files are the serialized leaves of
(params, opt_state, step).  That buys, for free, everything the paper's DU
semantics give data:

  * location transparency — restart anywhere the DU has (or can get) a
    replica;
  * replication — the DU carries a ``replication_factor``; sealing it
    hands dispersal and post-failure healing to the runtime's
    ReplicaManager/FaultManager (failure-domain-aware, chunk-striped),
    so a pod loss does not lose the run and NO checkpoint-layer code is
    involved in recovery;
  * affinity scheduling — the workload manager restarts the training CU
    near a checkpoint replica instead of dragging bytes across the DCN;
  * catalog — the coordination store maps ``ckpt:<run>`` to the DU chain.

Replication-factor enforcement requires the self-healing pipeline
(``enable_fault_manager=True`` on the Session/PilotManager); without it a
checkpoint still seals and restores, but keeps a single replica.

Leaves are stored whole (single-process container); a multi-host deployment
would store per-shard files keyed by shard index — the DU file namespace
already accommodates that (``leaf/<path>/shard<k>.npy``).

Restore is *resharding*: arrays come back as numpy and are re-placed by
whatever sharding the new mesh prescribes, so restarts may change topology
(elastic restart).
"""

from __future__ import annotations

import io
import json
import math
import zlib
from concurrent.futures import Future, ThreadPoolExecutor
from concurrent.futures import wait as futures_wait
from typing import Any, Dict, List, Optional, Tuple

import ml_dtypes  # noqa: F401 — registers bfloat16/float8 dtype names
import numpy as np
from numpy.lib import format as npy_format

from .. import tracing
from ..core import DataUnit, DataUnitDescription, DUState, RuntimeContext


class CheckpointError(RuntimeError):
    """An asynchronous checkpoint commit failed."""


class CheckpointTimeout(CheckpointError, TimeoutError):
    """``wait()`` deadline elapsed with commits still in flight."""


def flatten_tree(tree: Any, prefix: str = "") -> List[Tuple[str, Any]]:
    if isinstance(tree, dict):
        out = []
        for k in sorted(tree):
            out.extend(flatten_tree(tree[k], f"{prefix}{k}/"))
        return out
    return [(prefix.rstrip("/"), tree)]


def unflatten_tree(items: Dict[str, Any]) -> Any:
    root: Dict[str, Any] = {}
    for path, value in items.items():
        parts = path.split("/")
        node = root
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = value
    return root


#: Leading line of a leaf whose dtype the .npy header cannot name — the
#: ml_dtypes family (bfloat16, float8_*, int4), which .npy would store as
#: raw void.  The line carries the dtype name; the .npy after it holds the
#: same bits viewed as unsigned integers of the same width.
_DTYPE_LINE = b"\x93REPRO-DTYPE "


def encode_array(arr) -> bytes:
    """One leaf's bytes: its copy to the host and the .npy encoding, as a
    ``ckpt.encode`` span."""
    with tracing.span("ckpt.encode") as sp:
        a = np.asarray(arr)
        buf = io.BytesIO()
        if a.dtype.isbuiltin == 2:  # a dtype registered by ml_dtypes
            buf.write(_DTYPE_LINE + a.dtype.name.encode() + b"\n")
            a = a.view(np.dtype(f"u{a.dtype.itemsize}"))
        np.save(buf, a, allow_pickle=False)
        data = buf.getvalue()
        sp.attrs["bytes"] = len(data)
    return data


def decode_array(data) -> np.ndarray:
    """Inverse of :func:`encode_array`, bit for bit, dtype included; a
    ``ckpt.decode`` span.

    The leaf is a view of ``data`` at the ``.npy`` data offset: it shares
    memory with a ``bytearray`` (as ``fetch_du_file`` returns) and is one
    writable copy of immutable ``bytes``."""
    with tracing.span("ckpt.decode", bytes=len(data)):
        view = memoryview(data)
        name, off = None, 0
        if view[: len(_DTYPE_LINE)] == _DTYPE_LINE:
            nl = data.index(b"\n", len(_DTYPE_LINE))
            name, off = bytes(view[len(_DTYPE_LINE) : nl]).decode(), nl + 1
        magic_end = off + npy_format.MAGIC_LEN
        major, _ = npy_format.read_magic(io.BytesIO(bytes(view[off:magic_end])))
        len_end = magic_end + (2 if major == 1 else 4)
        head_end = len_end + int.from_bytes(view[magic_end:len_end], "little")
        read_header = (
            npy_format.read_array_header_1_0
            if major == 1
            else npy_format.read_array_header_2_0
        )
        shape, fortran_order, dtype = read_header(
            io.BytesIO(bytes(view[magic_end:head_end]))
        )
        a = np.frombuffer(data, dtype, count=math.prod(shape), offset=head_end)
        a = a.reshape(shape[::-1]).T if fortran_order else a.reshape(shape)
        if not a.flags.writeable:  # immutable bytes: one copy, as np.load gave
            a = a.copy(order="K")
        return a.view(np.dtype(name)) if name is not None else a


def tree_digest(tree: Any) -> int:
    """CRC32 over every leaf's path, dtype, shape and bytes — equal digests
    mean the two trees match bit for bit (used to check that a restored
    checkpoint is the state that was saved)."""
    crc = 0
    for path, leaf in flatten_tree(tree):
        a = np.ascontiguousarray(np.asarray(leaf))
        crc = zlib.crc32(f"{path}:{a.dtype.name}:{a.shape}".encode(), crc)
        crc = zlib.crc32(a.reshape(-1).view(np.uint8), crc)
    return crc


def checkpoint_files(
    step: int, run_name: str, params: Any, opt_state: Optional[Any] = None
) -> Dict[str, bytes]:
    """Serialize (step, params, opt_state) into a checkpoint DU file-set."""
    files = {"meta.json": json.dumps({"step": step, "run": run_name}).encode()}
    for path, leaf in flatten_tree({"params": params}):
        files[f"{path}.npy"] = encode_array(leaf)
    if opt_state is not None:
        for path, leaf in flatten_tree({"opt": opt_state}):
            files[f"{path}.npy"] = encode_array(leaf)
    return files


class Checkpointer:
    """Writes/reads checkpoint DUs; replication rides the runtime.

    Attach to a :class:`~repro.core.session.Session` (or a PilotManager —
    anything with ``.ctx``/``.cds``); a bare :class:`RuntimeContext` also
    works but then every ``save`` needs an explicit ``target``.

    Asynchronous commits run on ONE background executor (not a thread per
    save) and their failures are never swallowed: the next ``save()``
    re-raises a completed commit's error, and :meth:`wait` raises — a
    :class:`CheckpointError` for failed commits, :class:`CheckpointTimeout`
    when the deadline elapses with commits still in flight.
    """

    def __init__(
        self,
        runtime: Any,
        run_name: str = "run",
        replication_factor: int = 1,
    ):
        self.ctx: RuntimeContext = getattr(runtime, "ctx", runtime)
        self.cds = getattr(runtime, "cds", None)
        self.run_name = run_name
        self.replication_factor = replication_factor
        self._pool: Optional[ThreadPoolExecutor] = None
        self._pending: List[Future] = []

    # ----------------------------------------------------------------- save
    def _commit(self, du: DataUnit, step: int, target) -> DataUnit:
        pd = target
        if pd is None and self.cds is not None:
            pd = self.cds.choose_pilot_data(du.description)
        if pd is None:
            raise CheckpointError(
                f"{self.run_name} step {step}: no Pilot-Data target "
                f"(start one, or pass target=)"
            )
        self.ctx.store.hset(f"du:{du.id}", "state", DUState.PENDING)
        self.ctx.transfer_service.ingest(du, pd)
        # Sealing publishes the immutable manifest; with the fault manager
        # enabled the ReplicaManager now disperses the DU to its declared
        # replication_factor across failure domains — off this thread.
        with tracing.span("du.seal", du=du.id):
            du.seal()
        # the bytes now live in the Pilot-Data: a second, in-process copy
        # would double the host memory of every checkpoint
        du.drop_local_buffer()
        self.ctx.store.hset(f"ckpt:{self.run_name}", f"{step:08d}", du.id)
        return du

    def _commit_async(self, du: DataUnit, step: int, target) -> DataUnit:
        with tracing.span("ckpt.commit", du=du.id, step=step):
            return self._commit(du, step, target)

    def save(
        self,
        step: int,
        params: Any,
        opt_state: Optional[Any] = None,
        target=None,
        asynchronous: bool = False,
    ) -> DataUnit:
        """Write ``(step, params, opt_state)`` as a checkpoint DU: a
        ``ckpt.save`` span over the leaves' ``ckpt.encode``, the DU's
        ``du.create`` (its chunk manifest and CRCs), and, unless
        ``asynchronous``, its ``du.ingest`` and ``du.seal``; an
        asynchronous commit is a ``ckpt.commit`` span on its own thread."""
        self.check()  # surface any completed async commit's failure NOW
        with tracing.span("ckpt.save", step=step) as sp:
            desc = DataUnitDescription(
                name=f"{self.run_name}.ckpt{step:08d}",
                files=checkpoint_files(step, self.run_name, params, opt_state),
                replication_factor=self.replication_factor,
            )
            with tracing.span("du.create") as create:
                du = DataUnit(desc, self.ctx.store)
                create.attrs.update(du=du.id, bytes=du.size, chunks=du.n_chunks)
            sp.attrs["du"] = du.id
            self.ctx.register(du)
            if asynchronous:
                if self._pool is None:
                    self._pool = ThreadPoolExecutor(
                        max_workers=1, thread_name_prefix="ckpt-commit"
                    )
                self._pending.append(
                    self._pool.submit(self._commit_async, du, step, target)
                )
            else:
                self._commit(du, step, target)
        return du

    def check(self) -> None:
        """Re-raise the first failure among *completed* async commits
        (commits still running are left pending)."""
        still, failed = [], []
        for fut in self._pending:
            if not fut.done():
                still.append(fut)
            elif fut.exception() is not None:
                failed.append(fut.exception())
        self._pending = still
        if failed:
            raise CheckpointError(
                f"{self.run_name}: async checkpoint commit failed: "
                f"{failed[0]}"
            ) from failed[0]

    def wait(self, timeout: float = 30.0) -> None:
        """Block until every pending async commit settles.

        Raises :class:`CheckpointError` if any commit failed and
        :class:`CheckpointTimeout` if the deadline elapses first (the
        unfinished commits stay pending for a later ``wait``)."""
        pending, self._pending = self._pending, []
        done, not_done = futures_wait(pending, timeout=timeout)
        failed = [f.exception() for f in done if f.exception() is not None]
        self._pending = list(not_done)
        if failed:
            raise CheckpointError(
                f"{self.run_name}: async checkpoint commit failed: "
                f"{failed[0]}"
            ) from failed[0]
        if not_done:
            raise CheckpointTimeout(
                f"{self.run_name}: {len(not_done)} checkpoint commit(s) "
                f"still in flight after {timeout}s"
            )

    def close(self) -> None:
        if self._pool is not None:
            self._pool.shutdown(wait=True)
            self._pool = None

    # -------------------------------------------------------------- restore
    def latest_step(self) -> Optional[int]:
        index = self.ctx.store.hgetall(f"ckpt:{self.run_name}")
        return max((int(k) for k in index), default=None)

    def du_for_step(self, step: int) -> DataUnit:
        du_id = self.ctx.store.hget(f"ckpt:{self.run_name}", f"{step:08d}")
        if du_id is None:
            raise KeyError(f"no checkpoint for step {step}")
        return self.ctx.lookup(du_id)

    def restore(
        self, step: Optional[int] = None, location: Optional[str] = None
    ) -> Tuple[int, Any, Optional[Any]]:
        """Returns (step, params, opt_state) read from the nearest replica."""
        step = step if step is not None else self.latest_step()
        if step is None:
            raise KeyError(f"run {self.run_name!r} has no checkpoints")
        du = self.du_for_step(step)
        return load_checkpoint_du(self.ctx, du, location=location)


def load_checkpoint_du(
    ctx: RuntimeContext, du: DataUnit, location: Optional[str] = None
) -> Tuple[int, Any, Optional[Any]]:
    """Read a checkpoint DU (via the cheapest replica when location given)."""
    pd = None
    if du.locations:
        if location is not None and ctx.transfer_service is not None:
            pd, _ = ctx.transfer_service.resolve_access(du, location)
        if pd is None:
            pd = ctx.lookup(du.locations[0])

    def read(rel: str) -> bytes:
        return pd.fetch_du_file(du.id, rel) if pd is not None else du.read(rel)

    meta = json.loads(read("meta.json"))
    params_items, opt_items = {}, {}
    for rel in du.manifest:
        if not rel.endswith(".npy"):
            continue
        key = rel[: -len(".npy")]
        if key.startswith("params/"):
            params_items[key[len("params/") :]] = decode_array(read(rel))
        elif key.startswith("opt/"):
            opt_items[key[len("opt/") :]] = decode_array(read(rel))
    params = unflatten_tree(params_items)
    opt = unflatten_tree(opt_items) if opt_items else None
    return meta["step"], params, opt
