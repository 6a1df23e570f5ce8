"""The serve driver: the generator of serve traffic, the CU body it
drives, its submit loops and its comparison with the reference.

A traffic file (``traffic/<mix>.json``) with ``"driver": "serve"`` gives
only parameters:

- ``pilots`` × ``slots``: where the serve CUs run;
- ``replica``: true for one long-lived CU per slot that serves
  conversations until the deadline; false for a closed backlog of short
  CUs, ``queued_per_slot`` waiting behind each running one;
- ``turns``, ``user_tokens``, ``reply_tokens``: tables of sizes: turns per
  conversation, user tokens appended per turn, tokens generated per turn.
  Each is cycled through in an order drawn from the seed, so every seed
  serves the same sizes and only their order and the token ids change;
- ``sample``: how many finished conversations the comparison reads.

A conversation holds ``batch`` sequences in one ``DecodeEngine``: each
turn feeds the user tokens (after the last token of the previous reply)
with ``engine.prefill`` and decodes the reply with ``engine.generate``.  A
conversation ends after its turns, or before a turn that would pass
``max_len``; the next one starts a new engine.

The records are one :class:`harness.Request` per turn of a replica and
per short CU, and the ``(batch, live)`` of every ``jit_serve_step`` run.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import queue
import threading
import time
from typing import Dict, Iterator, List, Optional

import numpy as np

import harness
import weights as weights_mod

#: stream ids of the seeded generators
_SIZES, _TOKENS, _SAMPLE = 1, 2, 3
STEP = "jit_serve_step"


def rng(seed: int, stream: int, *more: int) -> np.random.Generator:
    return np.random.default_rng([seed, stream, *more])


def _cycled(values: List[int], gen: np.random.Generator) -> Iterator[int]:
    """Endless permutations of ``values``."""
    while True:
        yield from gen.permutation(values).tolist()


@dataclasses.dataclass
class Turn:
    user: int  # user tokens appended
    reply: int  # tokens generated


def conversations(traffic: Dict, seed: int, max_len: int, stream: int = 0) -> Iterator[List[Turn]]:
    """The endless, seeded sequence of conversations of one stream."""
    gen = rng(seed, _SIZES, stream)
    users = _cycled(traffic["user_tokens"], gen)
    replies = _cycled(traffic["reply_tokens"], gen)
    lengths = _cycled(traffic["turns"], gen)
    pending: Optional[Turn] = None
    while True:
        turns: List[Turn] = []
        used = 0
        for _ in range(next(lengths)):
            turn = pending or Turn(next(users), next(replies))
            if turn.user + turn.reply > max_len:
                raise ValueError(f"a turn of {turn} cannot fit max_len {max_len}")
            if used + turn.user + turn.reply > max_len:
                pending = turn  # opens the next conversation
                break
            pending = None
            turns.append(turn)
            used += turn.user + turn.reply
        yield turns


# ------------------------------------------------------------------ records
@dataclasses.dataclass
class Conversation:
    cu: int
    conv: int
    tokens: np.ndarray  # [batch, T]
    served: np.ndarray  # [T] bool: generated by the engine


class Recorder:
    """What the CU bodies report, shared with the driver under a lock."""

    def __init__(self, sample_cus=()):
        self.lock = threading.Lock()
        self.turns: List[harness.Request] = []
        self.steps: List[tuple] = []  # (batch, live) of every serve_step
        self.cus: Dict[int, harness.Request] = {}
        self.convs: List[Conversation] = []
        #: elements of the engines' caches stored below the stated precision
        self.narrowed = 0
        #: params of the CUs the comparison may sample, by CU index
        self.params: Dict[int, object] = {}
        self._sample_cus = set(sample_cus)
        self._longest = (-1, -1)  # (tokens, cu) whose params are kept

    def cu(self, index: int) -> harness.Request:
        with self.lock:
            return self.cus.setdefault(index, harness.Request())

    def keep_params(self, index: int, tokens: int, params) -> None:
        """Keep ``params`` if CU ``index`` is in the sample drawn from the
        seed, or has the longest conversation so far."""
        with self.lock:
            if index in self._sample_cus:
                self.params[index] = params
            if tokens > self._longest[0]:
                old = self._longest[1]
                if old not in self._sample_cus:
                    self.params.pop(old, None)
                self._longest = (tokens, index)
                self.params[index] = params


def narrowed_elements(cache, stated: str) -> int:
    """Elements of ``cache`` held in a type below ``stated``: an integer
    type, or a floating one with fewer bits or a shorter mantissa."""
    import jax
    import jax.numpy as jnp

    want = jnp.finfo(stated)
    bad = 0
    for leaf in jax.tree.leaves(cache):
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            got = jnp.finfo(leaf.dtype)
            if got.bits >= want.bits and got.nmant >= want.nmant:
                continue
        bad += int(leaf.size)
    return bad


# ------------------------------------------------------------------ CU body
class ServeCU:
    """The serve CU body: cold-start an engine from the weights DU, then
    serve its conversations.  Holds the run's shared state; the driver
    registers :meth:`__call__` as the CU executable."""

    def __init__(self, api, traffic, batch, max_len, vocab, cache_dtype, seed, recorder, annotate):
        self.api = api
        self.traffic = traffic
        self.batch = batch
        self.max_len = max_len
        self.vocab = vocab
        self.cache_dtype = cache_dtype
        self.seed = seed
        self.rec = recorder
        self.annotate = annotate
        self.ready: "queue.Queue[int]" = queue.Queue()  # replicas that are warm
        self.go = threading.Event()  # the window is open
        self.deadline = math.inf  # replicas stop at it; short CUs never do
        self._shared = conversations(traffic, seed, max_len)
        self._shared_list: List[List[Turn]] = []

    def plan(self, index: int) -> Iterator[List[Turn]]:
        """A replica serves its own endless stream; a short CU serves the
        ``index``-th conversation of the one shared stream."""
        if self.traffic["replica"]:
            return conversations(self.traffic, self.seed, self.max_len, stream=index)
        return iter([self._nth(index)])

    def _nth(self, index: int) -> List[Turn]:
        with self.rec.lock:
            while len(self._shared_list) <= index:
                self._shared_list.append(next(self._shared))
            return self._shared_list[index]

    def __call__(self, cu_ctx, weights_du: str, index: int, warm: bool):
        import jax

        from repro.serving import DecodeEngine, params_from_input

        t_cold0 = time.monotonic()
        with self.annotate("coldstart"):
            params = jax.device_put(params_from_input(cu_ctx, weights_du))
            jax.block_until_ready(params)
        coldstart = time.monotonic() - t_cold0
        if warm:
            self._warm_up(DecodeEngine, params)
        if index < 0:
            return 0  # a warm-up CU of the set-up serves nothing
        self.rec.cu(index).spans["coldstart"] = coldstart
        if self.traffic["replica"]:
            self.ready.put(index)
            self.go.wait()
        longest = 0
        for conv, turns in enumerate(self.plan(index)):
            if time.monotonic() >= self.deadline:
                break
            n = self._conversation(DecodeEngine, params, index, conv, turns)
            longest = max(longest, n)
        self.rec.keep_params(index, longest, params)
        return longest

    def _warm_up(self, engine_cls, params) -> None:
        """Compile every program the window will run: the step, slicing a
        step's output, and the reply concatenations of each size."""
        import jax.numpy as jnp

        with self.annotate("warmup"):
            engine = engine_cls(self.api, params, self.batch, self.max_len)
            first = engine.prefill(np.zeros((self.batch, 1), np.int32))
            out = engine.generate(first, 2)
            for r in sorted(set(self.traffic["reply_tokens"])):
                jnp.concatenate([first] * (r - 1), axis=1)
            np.asarray(out)

    def _conversation(self, engine_cls, params, index, conv, turns) -> int:
        gen = rng(self.seed, _TOKENS, index, conv)
        with self.annotate("engine"):
            engine = engine_cls(self.api, params, self.batch, self.max_len)
        parts: List[np.ndarray] = []
        served: List[np.ndarray] = []
        last = None
        pos = 0
        for turn in turns:
            if time.monotonic() >= self.deadline:
                break
            user = gen.integers(0, self.vocab, (self.batch, turn.user), dtype=np.int32)
            fed = user if last is None else np.concatenate([last, user], axis=1)
            t_start = time.monotonic()
            with self.annotate("prefill"):
                first = engine.prefill(fed)
                first_h = np.asarray(first)
            t_first = time.monotonic()
            with self.annotate("decode"):
                rest = np.asarray(engine.generate(first, turn.reply - 1))
            t_end = time.monotonic()
            steps = fed.shape[1] + turn.reply - 1
            with self.rec.lock:
                self.rec.turns.append(
                    harness.Request(t_submit=t_start, t_end=t_end, units=self.batch * turn.reply, t_first=t_first)
                )
                self.rec.steps += [(self.batch, p + 1) for p in range(pos, pos + steps)]
            pos += steps
            parts += [user, first_h, rest]
            served += [np.zeros(turn.user, bool), np.ones(turn.reply, bool)]
            last = rest[:, -1:]
        if not parts:
            return 0
        tokens = np.concatenate(parts, axis=1)
        narrowed = narrowed_elements(engine.cache, self.cache_dtype)
        with self.rec.lock:
            self.rec.convs.append(Conversation(index, conv, tokens, np.concatenate(served)))
            self.rec.narrowed += narrowed
        return tokens.shape[1]


# ------------------------------------------------------------------ driver
class Driver:
    """Set-up, the window and the comparison of one run of a serve cell."""

    def __init__(self, cell, api, seed: int, annotate):
        self.cell = cell
        self.tr = cell.traffic
        self.seed = seed
        self.n_slots = self.tr["pilots"] * self.tr["slots"]
        self.backlog = self.n_slots * (1 + self.tr["queued_per_slot"])
        if self.tr["replica"]:
            sample_cus = range(self.n_slots)
        else:
            pick = rng(seed, _SAMPLE)
            n = min(self.tr["sample"] - 1, self.backlog)
            sample_cus = pick.choice(self.backlog, size=n, replace=False).tolist()
        self.rec = Recorder(sample_cus)
        self.body = ServeCU(
            api, self.tr, cell.batch, cell.max_len, cell.sizes["vocab_size"],
            cell.config["cache_dtype"], seed, self.rec, annotate,
        )
        self.exe = f"bench-serve:{cell.name}:{seed}:{id(self.body)}"
        self.futures: Dict[int, object] = {}
        self.kept: Dict[int, object] = {}
        self.convs: List[Conversation] = []

    def start(self, session, weights_du, hosts) -> None:
        """Start the pilots and warm every program the window runs."""
        from repro.core import FUNCTIONS

        FUNCTIONS.register(self.exe, self.body)
        self.session, self.du = session, weights_du
        for h in hosts:
            session.start_pilot(resource_url=f"sim://{h}", slots=self.tr["slots"])
        if not self.tr["replica"]:
            self._submit(-1, warm=True).result(timeout=harness.WAIT_S)
            return
        self.futures = {i: self._submit(i, warm=True) for i in range(self.n_slots)}
        warm = 0
        while warm < self.n_slots:
            try:
                self.body.ready.get(timeout=1.0)
                warm += 1
            except queue.Empty:
                ended = [f.error for f in self.futures.values() if f.done()]
                if ended:
                    raise RuntimeError(f"a replica ended in set-up: {ended}")

    def _submit(self, index: int, warm: bool = False):
        return self.session.submit_cu(
            executable=self.exe, args=(self.du.id, index, warm), input_data=[self.du], max_retries=0
        )

    def window(self, deadline: float) -> harness.Window:
        """Serve until ``deadline``, and wait for what is in flight."""
        if self.tr["replica"]:
            self.body.deadline = deadline
            self.body.go.set()
            for f in self.futures.values():
                f.wait(timeout=harness.WAIT_S)
        else:
            self._closed_loop(deadline)
        done = {i for i, f in self.futures.items() if f.state == "Done"}
        for i, f in self.futures.items():
            r = self.rec.cu(i)
            r.ok = i in done
            r.spans["queue"] = f.timings.stage_start - f.timings.submitted
        turns = list(self.rec.turns)
        if self.tr["replica"]:
            requests = turns
        else:
            requests = [self.rec.cus[i] for i in sorted(self.futures)]
            for r in requests:
                r.units *= r.ok
        ends = [r.t_end for r in requests] + [r.t_end for r in turns]
        self._sample()
        harness.log(f"window: {len(turns)} turns over {len(self.futures)} CUs ({len(done)} done)")
        return harness.Window(
            requests=requests,
            steps={STEP: list(self.rec.steps)},
            t_end=max(ends, default=time.monotonic()),
            failed=len(self.futures) - len(done),
        )

    def _closed_loop(self, deadline: float) -> None:
        """Keep ``backlog`` CUs submitted until the deadline, then drain."""
        finished: "queue.Queue" = queue.Queue()

        def launch(i: int) -> None:
            r = self.rec.cu(i)
            r.t_submit = time.monotonic()
            r.units = self.cell.batch * sum(t.reply for t in self.body._nth(i))
            f = self._submit(i)
            self.futures[i] = f
            f.add_done_callback(lambda _f, i=i: finished.put((i, time.monotonic())))

        for i in range(self.backlog):
            launch(i)
        open_ = self.backlog
        while open_:
            i, t = finished.get(timeout=harness.WAIT_S)
            self.rec.cu(i).t_end = t
            open_ -= 1
            if time.monotonic() < deadline:
                launch(len(self.futures))
                open_ += 1

    def _sample(self) -> None:
        """Keep the finished conversations the comparison reads, drawn from
        the seed with the longest among them, and their CUs' weights."""
        self.kept = dict(self.rec.params)
        self.rec.params.clear()
        convs = [c for c in self.rec.convs if c.cu in self.kept]
        if self.tr["replica"] and convs:
            longest = max(convs, key=lambda c: c.tokens.shape[1])
            rest = [c for c in convs if c is not longest]
            pick = rng(self.seed, _SAMPLE, 1)
            n = min(self.tr["sample"] - 1, len(rest))
            convs = [longest] + [rest[i] for i in sorted(pick.choice(len(rest), n, replace=False))]
        self.convs = convs

    def compare(self, shapes, control: bool = False):
        """The numbers compared, the served tokens they cover and, with
        ``control``, the control's numbers on the same requests."""
        import jax

        want = weights_mod.make_weights(shapes, self.seed)
        mismatched = sum(weights_mod.count_mismatches(p, want) for p in self.kept.values())
        self.kept.clear()
        gc.collect()
        t = time.monotonic()
        with jax.default_matmul_precision("highest"):
            gap, n = widest_gap(self.cell, want, self.convs)
            harness.log(f"reference: {n} served tokens of {len(self.convs)} requests compared "
                        f"in {time.monotonic() - t:.3f} s")
            ctrl = None
            if control:
                # the reference in the program's place: it reads the seed's
                # weights and keeps no cache
                ctrl = {"weights_mismatch": 0, "cache_narrowed": 0,
                        "logit_gap": widest_gap(self.cell, want, self.convs, control=True)[0]}
        checks = {"weights_mismatch": mismatched, "cache_narrowed": self.rec.narrowed, "logit_gap": gap}
        return checks, n, ctrl


# --------------------------------------------------------- the comparison
def gap_fn(cell, control: bool = False):
    """jitted (weights, tokens [R, T], served [T]) → the widest gap by which
    a served token's reference logit lies below the reference's best at
    its position.  With ``control`` the served token is replaced by the
    one the float8 reference puts first."""
    import jax
    import jax.numpy as jnp

    ref, sizes = cell.reference, cell.sizes

    def widest(w, tokens, served):
        lg = ref.logits(w, sizes, tokens)[:, :-1]
        top = lg.max(axis=-1)
        if control:
            pick = ref.logits(w, sizes, tokens, fp8=True)[:, :-1].argmax(axis=-1)
        else:
            pick = tokens[:, 1:]
        got = jnp.take_along_axis(lg, pick[..., None], axis=-1)[..., 0]
        return jnp.max(jnp.where(served[None, 1:], top - got, 0.0))

    return jax.jit(widest)


def widest_gap(cell, w, convs, control: bool = False) -> tuple:
    """(widest gap, served tokens compared) over ``convs``."""
    fn = gap_fn(cell, control)
    rows, block = cell.reference.ROWS, cell.reference.BLOCK
    worst, n = 0.0, 0
    for c in convs:
        t = c.tokens.shape[1]
        pad = -t % block
        tokens = np.pad(c.tokens, ((0, 0), (0, pad)))
        served = np.pad(c.served, (0, pad))
        for r in range(0, tokens.shape[0], rows):
            worst = max(worst, float(fn(w, tokens[r : r + rows], served)))
        n += int(c.served.sum()) * c.tokens.shape[0]
    return worst, n
