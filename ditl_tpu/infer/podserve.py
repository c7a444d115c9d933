"""Multi-host (pod) serving: every process runs the same SPMD decode,
process 0 talks HTTP.

The reference's serving story is an external endpoint; its multi-node story
is two hand-launched ranks that never communicate after a startup barrier
(ref ``scripts/run_node0.sh``, ``src/distributed_inference.py:18``). Here a
sharded model spanning several hosts must run its generate program on EVERY
process simultaneously (an XLA SPMD program is a lockstep pod-wide program),
while HTTP naturally arrives at one host. This module bridges the two:

- Process 0 owns the listener. Its request threads hand work to a single
  **pump thread** which, on a fixed cadence, broadcasts one fixed-layout
  header (+ payload when work is pending) to all processes
  (``multihost_utils.broadcast_one_to_all`` — the same collective substrate
  as training).
- Every process (0 included) then calls the *identical*
  ``Generator.generate_tokens`` on the broadcast prompts; GSPMD executes the
  sharded program across the pod. Results are fully replicated, so process 0
  answers HTTP locally and the others discard.
- At ``jax.process_count() == 1`` the broadcasts are identity and this
  degenerates to a slightly-buffered Generator — which is how the protocol
  is unit-tested (tests/test_podserve.py); multi-host execution reuses the
  exact code path.

Protocol (per tick): header ``(8,) int32`` =
``[opcode, batch, prompt_len, max_new, temp_bits, top_p_bits, seed, top_k]``
(floats bit-cast); opcode 0 = idle, 1 = generate (followed by an
``(batch, prompt_len)`` ids broadcast and a ``(batch,)`` lengths broadcast),
2 = shutdown. Fixed layout means every process always issues the same
collective sequence — the SPMD discipline that makes this deadlock-free.
"""

from __future__ import annotations

import queue
import threading

import numpy as np

from ditl_tpu.infer.engine import GenerateConfig, Generator
from ditl_tpu.utils.logging import get_logger

logger = get_logger(__name__)

__all__ = [
    "PodGenerator", "worker_loop",
    "PodContinuousDriver", "continuous_worker_loop",
]

_IDLE, _GENERATE, _SHUTDOWN, _CTICK = 0, 1, 2, 3


def _f2i(x: float) -> int:
    return int(np.float32(x).view(np.int32))


def _i2f(x: int) -> float:
    return float(np.int32(x).view(np.float32))


def _broadcast(arr: np.ndarray) -> np.ndarray:
    from jax.experimental import multihost_utils

    return np.asarray(multihost_utils.broadcast_one_to_all(arr))


def _statuses_agree(ok: bool) -> bool:
    """Post-tick status collective: all processes exchange an ok/fail byte.

    A one-sided failure (transient device error on one host mid-generate)
    would otherwise leave that process waiting at the next header broadcast
    while the others are still inside the generate program's collectives —
    a silent, permanent desync. Every process calls this after every generate
    tick; the gathered vector is identical pod-wide, so all processes take
    the same shutdown decision when statuses diverge."""
    from jax.experimental import multihost_utils

    statuses = np.asarray(
        multihost_utils.process_allgather(np.asarray([1 if ok else 0], np.int32))
    ).reshape(-1)
    return bool(statuses.min() == statuses.max())


def _status_fingerprints_agree(ok: bool, fingerprint: int) -> bool:
    """Continuous-tick status collective carrying the engine's scheduler
    FINGERPRINT (ContinuousEngine.scheduler_fingerprint) alongside the
    ok/fail byte: replicas whose page allocators or slot schedules diverge
    — even while every tick 'succeeds' locally — produce different
    digests, and the whole pod shuts down loudly instead of silently
    gathering different pages inside the same SPMD program."""
    from jax.experimental import multihost_utils

    statuses = np.asarray(multihost_utils.process_allgather(
        np.asarray([1 if ok else 0, fingerprint], np.int64)
    )).reshape(-1, 2)
    return bool((statuses.min(axis=0) == statuses.max(axis=0)).all())


class _Job:
    def __init__(self, token_lists, gen):
        self.token_lists = token_lists
        self.gen = gen
        self.done = threading.Event()
        self.result = None
        self.error: BaseException | None = None


def _run_tick(
    generator: Generator,
    header: np.ndarray,
    ids: np.ndarray | None,
    lengths: np.ndarray | None,
):
    """Execute one broadcast generate tick — identical on every process."""
    _, batch, _, max_new, temp_bits, top_p_bits, seed, top_k = (
        int(v) for v in header
    )
    token_lists = [ids[i, : lengths[i]].tolist() for i in range(batch)]
    gen = GenerateConfig(
        max_new_tokens=max_new,
        temperature=_i2f(temp_bits),
        top_k=top_k,
        top_p=_i2f(top_p_bits),
        seed=seed,
    )
    return generator.generate_tokens(token_lists, gen)


class PodGenerator:
    """Process-0 front: queues HTTP requests and pumps them through the
    pod-wide broadcast protocol. Exposes the ``Generator`` surface the HTTP
    handler uses (``generate``/``generate_tokens``/``tokenizer``)."""

    def __init__(self, generator: Generator, *, poll_s: float = 0.05):
        self.generator = generator
        self.tokenizer = generator.tokenizer
        self.poll_s = poll_s
        self._jobs: queue.Queue[_Job] = queue.Queue()
        self._stop = False
        # Guards the (_stop check, enqueue) pair in generate_tokens against
        # close(): without it a job could slip in after the pump drained the
        # queue and block its waiter forever.
        self._submit_lock = threading.Lock()
        self._pump = threading.Thread(target=self._pump_loop, daemon=True)
        self._pump.start()

    # -- pump (the only thread issuing collectives on process 0) -------------

    def _pump_loop(self) -> None:
        while True:
            try:
                job = self._jobs.get(timeout=self.poll_s)
            except queue.Empty:
                job = None
            if self._stop:
                _broadcast(np.asarray([_SHUTDOWN, 0, 0, 0, 0, 0, 0, 0], np.int32))
                # Fail every queued waiter — leaving any job un-signalled
                # would deadlock its HTTP thread in done.wait(). The submit
                # lock guarantees nothing is enqueued after this drain.
                with self._submit_lock:
                    pending = [job] if job is not None else []
                    while True:
                        try:
                            pending.append(self._jobs.get_nowait())
                        except queue.Empty:
                            break
                    for j in pending:
                        j.error = RuntimeError("pod serving stopped")
                        j.done.set()
                return
            if job is None:
                _broadcast(np.asarray([_IDLE, 0, 0, 0, 0, 0, 0, 0], np.int32))
                continue
            try:
                gen = job.gen
                batch = len(job.token_lists)
                plen = max(1, max(len(t) for t in job.token_lists))
                ids = np.zeros((batch, plen), np.int32)
                lengths = np.zeros((batch,), np.int32)
                for i, toks in enumerate(job.token_lists):
                    ids[i, : len(toks)] = toks
                    lengths[i] = len(toks)
                header = np.asarray(
                    [
                        _GENERATE, batch, plen, gen.max_new_tokens,
                        _f2i(gen.temperature), _f2i(gen.top_p), gen.seed,
                        gen.top_k,
                    ],
                    np.int32,
                )
            except BaseException as e:  # noqa: BLE001 — handed to the waiter
                # Packing failed BEFORE anything was broadcast: the pod never
                # saw this tick, so fail the one job and keep serving.
                job.error = e
                job.done.set()
                continue
            try:
                _broadcast(header)
                ids = _broadcast(ids)
                lengths = _broadcast(lengths)
            except BaseException as e:  # noqa: BLE001
                # A failure mid-broadcast is FATAL: workers that received the
                # header are already inside the ids broadcast / post-tick
                # allgather, so continuing to the next job would misalign the
                # pod's collective sequence and hang everyone (ADVICE r2) —
                # same shutdown path as a status divergence.
                job.error = e
                job.done.set()
                logger.exception(
                    "pod broadcast failed mid-tick; stopping pod serving "
                    "(collective sequence can no longer be trusted)"
                )
                with self._submit_lock:
                    self._stop = True
                    while True:
                        try:
                            j = self._jobs.get_nowait()
                        except queue.Empty:
                            break
                        j.error = RuntimeError(
                            "pod serving stopped (broadcast failure)"
                        )
                        j.done.set()
                return
            ok = True
            try:
                job.result = _run_tick(self.generator, header, ids, lengths)
            except BaseException as e:  # noqa: BLE001 — handed to the waiter
                job.error = e
                ok = False
            if not _statuses_agree(ok):
                # One-sided failure: the pod can no longer be assumed in
                # lockstep. Workers saw the same divergent vector and are
                # exiting their loops, so do NOT broadcast further (a
                # collective with absent participants hangs) — fail local
                # waiters and stop serving.
                job.error = job.error or RuntimeError(
                    "pod tick status diverged across processes"
                )
                job.done.set()
                logger.error(
                    "pod tick status diverged across processes; stopping pod "
                    "serving (workers have shut down)"
                )
                with self._submit_lock:
                    self._stop = True
                    while True:
                        try:
                            j = self._jobs.get_nowait()
                        except queue.Empty:
                            break
                        j.error = RuntimeError("pod serving stopped (desync)")
                        j.done.set()
                return
            job.done.set()

    # -- Generator surface ----------------------------------------------------

    def generate_tokens(
        self,
        token_lists: list[list[int]],
        gen: GenerateConfig | None = None,
        adapter_ids=None,
    ) -> list[list[int]]:
        if adapter_ids is not None:
            raise ValueError(
                "multi-LoRA adapter selection is not carried by the pod "
                "broadcast protocol; serve adapters without --pod"
            )
        if not token_lists:
            return []
        gen = gen or GenerateConfig()
        token_lists = [t if t else [self.tokenizer.bos_id] for t in token_lists]
        job = _Job(token_lists, gen)
        with self._submit_lock:
            if self._stop:
                raise RuntimeError("pod serving stopped")
            self._jobs.put(job)
        job.done.wait()
        if job.error is not None:
            raise job.error
        return job.result

    def generate(
        self,
        prompts: list[str],
        gen: GenerateConfig | None = None,
        adapter_ids=None,
    ) -> list[str]:
        encoded = [
            [self.tokenizer.bos_id] + self.tokenizer.encode(p) for p in prompts
        ]
        return [
            self.tokenizer.decode(t)
            for t in self.generate_tokens(encoded, gen, adapter_ids)
        ]

    def close(self) -> None:
        """Broadcast shutdown to the pod and stop the pump. Waits long enough
        for an in-flight generate (first-request compiles routinely exceed
        10s) to drain — exiting before the shutdown opcode goes out would
        strand every worker in its blocking broadcast."""
        self._stop = True
        self._pump.join(timeout=600)
        if self._pump.is_alive():
            logger.error(
                "pod pump did not drain within 600s; workers may be left "
                "blocked in their broadcast loop"
            )


def worker_loop(generator: Generator) -> None:
    """Run on every process with ``jax.process_index() != 0``: mirror process
    0's collective sequence forever, executing each generate tick, until a
    shutdown opcode arrives. Results are replicated; non-zero processes
    simply drop them."""
    logger.info("pod serve worker: entering broadcast loop")
    while True:
        header = _broadcast(np.zeros((8,), np.int32))
        op = int(header[0])
        if op == _SHUTDOWN:
            logger.info("pod serve worker: shutdown")
            return
        if op == _IDLE:
            continue
        batch, plen = int(header[1]), int(header[2])
        ids = _broadcast(np.zeros((batch, plen), np.int32))
        lengths = _broadcast(np.zeros((batch,), np.int32))
        ok = True
        try:
            _run_tick(generator, header, ids, lengths)
        except Exception:
            # Deterministic per-request errors (validation, OOM-at-shape)
            # raise identically on every process; the status collective below
            # confirms that before continuing. A worker that died here
            # instead would strand the whole pod at the next broadcast.
            ok = False
            logger.exception("pod serve worker: tick failed")
        if not _statuses_agree(ok):
            # One-sided failure — the pod is desynced; every process saw the
            # same divergent status vector, so all exit together.
            logger.error(
                "pod serve worker: tick status diverged across processes; "
                "shutting down"
            )
            return


# ---------------------------------------------------------------------------
# Pod-wide continuous batching
# ---------------------------------------------------------------------------
#
# The lock-step PodGenerator broadcasts whole generate calls; a continuous
# engine instead needs every process to run the SAME scheduler ticks on the
# same state. The protocol broadcasts scheduler INPUTS (submits + cancels)
# once per tick; each process applies them to its own ContinuousEngine
# replica (deterministic: same seeds, same FIFO order, same slot math) and
# calls engine.step() — the tick's prefill/decode programs are then
# pod-wide SPMD programs over the engine's mesh. Results are replicated;
# process 0 answers HTTP.
#
# CTICK payload: header [_CTICK, n_submits, ids_total, n_cancels, 0...];
# then meta (n_submits, 5) int32 = [prompt_len, max_new, temp_bits,
# top_p_bits, seed]; ids (ids_total,) int32 (prompts concatenated);
# cancels (n_cancels,) int32 (req ids). A post-tick status collective
# (_statuses_agree) detects one-sided failures exactly as in lock-step
# pod serving.


def _apply_ctick(engine, meta: np.ndarray, ids: np.ndarray, cancels: np.ndarray,
                 streams: list | None = None, traces: list | None = None):
    """Apply one broadcast tick's scheduler inputs, then run one tick.
    Returns the submitted request ids (identical on every process).
    ``streams`` (process 0 only) attaches per-request stream queues at
    submit time — before the tick's step, so first-tick chunks are not
    lost; worker replicas stream to nowhere. ``traces`` (process 0 only,
    same shape) attaches upstream span contexts: tracing is host-side
    bookkeeping like streams, never broadcast, so worker replicas simply
    record no spans — scheduler state stays identical pod-wide."""
    from ditl_tpu.infer.continuous import QueueFullError

    rids = []
    off = 0
    for i, row in enumerate(meta):
        plen, max_new, temp_bits, top_p_bits, seed, adapter = (
            int(v) for v in row
        )
        prompt = ids[off: off + plen].tolist()
        off += plen
        try:
            rids.append(engine.submit(
                prompt, max_new_tokens=max_new, temperature=_i2f(temp_bits),
                top_p=_i2f(top_p_bits), seed=seed,
                stream=streams[i] if streams is not None else None,
                adapter_id=adapter or None,
                trace=traces[i] if traces is not None else None,
            ))
        except (ValueError, QueueFullError) as e:
            # Deterministic per-request rejection: the same submit fails
            # identically on every process (same engine state), so the pod
            # stays in lockstep while only this request errors.
            rids.append(e)
    for rid in cancels:
        engine.cancel(int(rid))
    engine.step()
    return rids


class PodContinuousDriver:
    """Process-0 driver for pod-wide continuous batching. Exposes the
    ``ThreadedEngine`` surface the HTTP server uses (``generate_one``,
    ``stream_one``, ``cancel``, ``queue_full``, ``close``) while pumping
    scheduler inputs through the pod broadcast so every process ticks the
    same engine state. At ``process_count == 1`` the broadcasts are
    identity and this degenerates to a broadcast-framed ThreadedEngine —
    how the protocol is unit-tested."""

    def __init__(self, engine, *, poll_s: float = 0.02):
        # what a pod cannot carry yet of the engine's cache (infer/page_format.py)
        engine.page_format.refuse("pod")
        self._engine = engine
        # Per-host wall-clock calibration would desync pod tick decisions.
        engine.freeze_spec_threshold()
        self.tokenizer = engine.tokenizer
        self.poll_s = poll_s
        self._lock = threading.Lock()
        self._staged: list[tuple] = []  # (prompt, max_new, temp, top_p, seed, adapter, ticket)
        self._cancels: set[int] = set()
        self._tickets: dict[int, "_Ticket"] = {}
        self._inflight = 0  # batch swapped out of _staged, not yet submitted
        self._workers_down = False  # divergence detected: never broadcast again
        self._seq = 0  # monotonic default-seed counter (never reset)
        self._stop = False
        self._error: BaseException | None = None
        self._cond = threading.Condition(self._lock)
        self._pump = threading.Thread(target=self._pump_loop, daemon=True)
        self._pump.start()

    def stats(self) -> dict:
        eng_stats = self._engine.stats()
        eng_stats["pod"] = True
        eng_stats["staged"] = len(self._staged)
        return eng_stats

    @property
    def metrics(self):
        """Coordinator-replica telemetry (telemetry/serving.py) for the
        /metrics route — every replica ticks identical scheduler state, so
        process 0's counters ARE the pod's."""
        return self._engine.metrics

    @property
    def queue_full(self) -> bool:
        # Lock-free on purpose: _stage calls this while holding _cond (the
        # same non-reentrant lock), and the check is best-effort anyway —
        # len() reads of a deque/list are atomic under the GIL.
        eng = self._engine
        if eng.max_queue is None:
            return False
        return (len(eng._queue) + len(self._staged) + self._inflight
                >= eng.max_queue)

    def _pump_loop(self) -> None:
        import time as _time

        while True:
            with self._cond:
                while (not self._stop and not self._staged and not self._cancels
                       and self._engine.pending == 0):
                    self._cond.wait(timeout=self.poll_s)
                if self._stop:
                    staged, self._staged = self._staged, []
                    break
                staged, self._staged = self._staged, []
                cancels, self._cancels = self._cancels, set()
                self._inflight = len(staged)
            try:
                self._tick(staged, sorted(cancels))
            except BaseException as e:  # noqa: BLE001
                logger.exception("pod continuous driver died")
                if not self._workers_down:
                    # Wake workers parked in their header broadcast so they
                    # exit instead of hanging forever. Skipped after a
                    # status divergence (the workers already shut down — a
                    # collective with absent participants would hang US).
                    try:
                        _broadcast(np.asarray(
                            [_SHUTDOWN, 0, 0, 0, 0, 0, 0, 0], np.int32
                        ))
                    except Exception:
                        logger.exception("shutdown broadcast failed")
                with self._cond:
                    self._error = e
                    self._stop = True
                    # Fail EVERY outstanding waiter: registered tickets,
                    # the in-flight batch (whose tickets may not have been
                    # registered yet), and anything staged during the tick
                    # — an unset ticket event is a permanently hung HTTP
                    # connection.
                    for t in self._tickets.values():
                        t.fail(e)
                    self._tickets.clear()
                    for (*_, t) in staged:
                        t.fail(e)
                    for (*_, t) in self._staged:
                        t.fail(e)
                    self._staged.clear()
                    self._cond.notify_all()
                return
        # shutdown: one final broadcast releases the workers
        _broadcast(np.asarray([_SHUTDOWN, 0, 0, 0, 0, 0, 0, 0], np.int32))
        with self._cond:
            err = RuntimeError("pod serving stopped")
            for t in self._tickets.values():
                t.fail(err)
            for (*_, ticket) in staged:
                ticket.fail(err)
            self._tickets.clear()
            self._cond.notify_all()

    def _tick(self, staged, cancels) -> None:
        try:
            metas, all_ids = [], []
            for (prompt, max_new, temp, top_p, seed, adapter, _t) in staged:
                metas.append([
                    len(prompt), max_new, _f2i(temp), _f2i(top_p), seed,
                    adapter,
                ])
                all_ids.extend(prompt)
            meta = np.asarray(metas, np.int32).reshape(len(staged), 6)
            ids = np.asarray(all_ids, np.int32)
            cc = np.asarray(cancels, np.int32)
        except Exception as e:
            # Packing failed before anything was broadcast: fail this batch
            # only — the pod never saw the tick, so serving continues.
            with self._cond:
                self._inflight = 0
                for (*_, ticket) in staged:
                    ticket.fail(e)
            return
        header = np.asarray(
            [_CTICK, len(staged), len(all_ids), len(cc), 0, 0, 0, 0], np.int32
        )
        _broadcast(header)
        if len(staged):
            _broadcast(meta)
            _broadcast(ids)
        if len(cc):
            _broadcast(cc)
        ok = True
        rids = []
        try:
            rids = _apply_ctick(
                self._engine, meta, ids, cc,
                streams=[t.stream for (*_, t) in staged],
                traces=[t.trace for (*_, t) in staged],
            )
        except Exception as e:  # noqa: BLE001 — surfaced via tickets
            ok = False
            err = e
        if not _status_fingerprints_agree(
            ok, self._engine.scheduler_fingerprint() if ok else 0
        ):
            self._workers_down = True
            raise RuntimeError(
                "pod tick status/scheduler-state diverged across processes "
                "(workers have shut down)"
            )
        with self._cond:
            self._inflight = 0
            if not ok:
                for (*_, ticket) in staged:
                    ticket.fail(err)
                return
            for (*_, ticket), rid in zip(staged, rids):
                if isinstance(rid, BaseException):
                    ticket.fail(rid)  # deterministic per-request rejection
                    continue
                ticket.req_id = rid
                if ticket.abandoned:
                    # generate_many failed mid-stage after this copy was
                    # staged: cancel on the next tick, never register.
                    self._cancels.add(rid)
                    continue
                self._tickets[rid] = ticket
            for req in self._engine.take_finished():
                t = self._tickets.pop(req.req_id, None)
                if t is not None:
                    t.finish(req.tokens)
            self._cond.notify_all()

    # -- ThreadedEngine surface ----------------------------------------------

    def _stage(self, prompt_tokens, max_new_tokens, temperature, top_p, seed,
               stream=None, adapter_id=None, grammar=None,
               trace=None) -> "_Ticket":
        from ditl_tpu.infer.continuous import BadRequestError, QueueFullError

        if grammar is not None:
            # The server CLI already refuses --fsm-capacity with --pod, so a
            # guided request can only reach here via a direct driver call;
            # ValueError (not TypeError) means request validation — the
            # server's completion handlers map it to HTTP 400.
            raise BadRequestError(
                "guided decoding does not compose with --pod serving (the "
                "tick broadcast does not carry grammar registrations)"
            )
        gen = self._engine.gen
        ticket = _Ticket(stream, trace)
        prompt = list(prompt_tokens) or [self.tokenizer.bos_id]
        max_new = (max_new_tokens if max_new_tokens is not None
                   else gen.max_new_tokens)
        # Validate on the HTTP thread: a bad request must fail HERE, not
        # inside the broadcast tick it would share with innocent requests.
        self._engine.validate_request(prompt, max_new)
        if seed is not None and not (-2**31 <= int(seed) < 2**31):
            raise BadRequestError("seed must fit in int32")
        if not (0 < max_new < 2**31):
            raise BadRequestError("max_tokens out of range")
        adapter = int(adapter_id or 0)
        if adapter and not (
            self._engine.multi_lora
            and 0 <= adapter < self._engine.n_adapters
        ):
            raise BadRequestError(
                f"adapter_id {adapter} invalid for this engine"
            )
        with self._cond:
            if self._stop:
                raise RuntimeError("pod serving stopped") from self._error
            if self.queue_full:
                # The driver-level rejection bypasses engine.submit (the
                # other queue_full.inc site) — count it here or pod-mode
                # overload would read 0 on the 429-rate alert the
                # troubleshooting doc tells operators to build.
                self._engine.metrics.queue_full.inc()
                raise QueueFullError("admission queue full (pod)")
            self._staged.append((
                prompt,
                max_new,
                gen.temperature if temperature is None else float(temperature),
                gen.top_p if top_p is None else float(top_p),
                int(seed) if seed is not None else
                # Driver-level monotonic counter: unlike engine._next_id +
                # len(staged) (which races with an in-flight tick swapping
                # the staged list), _seq only moves forward, so concurrent
                # default-seeded requests never collide.
                self._engine._base_seed + self._seq,
                adapter,
                ticket,
            ))
            self._seq += 1
            self._cond.notify_all()
        return ticket

    @property
    def multi_lora(self) -> bool:
        return self._engine.multi_lora

    # The server consults this for HEADER-derived deadlines (the gateway
    # stamps every relay with its remaining budget): a best-effort hint is
    # dropped rather than 400-ing every gateway-routed request. An explicit
    # client `deadline_s` payload still goes through _reject_deadline.
    supports_deadlines = False
    # Same stance for SLO classes (ISSUE 8): queue order is replicated
    # scheduler state, and staging does not broadcast a class lane, so a
    # non-default class on one process would desync admission order pod-
    # wide. Header-derived hints are dropped by the server; explicit
    # payload values go through _reject_slo_class.
    supports_slo_classes = False

    @staticmethod
    def _reject_slo_class(slo_class) -> None:
        """Pod serving carries no SLO classes: the tick broadcast stages
        requests FIFO and every replica must sort its queue identically.
        Reject-don't-drop for explicit client values."""
        if slo_class is not None and slo_class != "interactive":
            from ditl_tpu.infer.continuous import BadRequestError

            raise BadRequestError(
                "slo_class does not compose with --pod serving (the tick "
                "broadcast stages requests FIFO; a per-process priority "
                "queue would desync the replicated scheduler)"
            )

    @staticmethod
    def _reject_deadline(deadline_s) -> None:
        """Pod serving carries no deadlines: the tick broadcast replicates
        the scheduler on every process, and per-process wall-clock expiry
        sweeps would desync the replicas (divergent slot tables -> SPMD
        fingerprint shutdown). Reject-don't-drop, so a client's deadline is
        never silently ignored."""
        if deadline_s is not None:
            from ditl_tpu.infer.continuous import BadRequestError

            raise BadRequestError(
                "deadline_s does not compose with --pod serving (the tick "
                "broadcast carries no deadlines; per-process clocks would "
                "desync the replicated scheduler)"
            )

    @property
    def tracer(self):
        """Process-0 engine's tracer — make_server derives the HTTP span
        layer from it, same as solo serving."""
        return self._engine.tracer

    def generate_one(self, prompt_tokens, *, max_new_tokens=None,
                     temperature=None, top_p=None, seed=None,
                     adapter_id=None, grammar=None,
                     deadline_s=None, slo_class=None, trace=None,
                     tenant=None) -> list[int]:
        # ``tenant`` (ISSUE 15) is accepted-and-dropped: the tick
        # broadcast carries no tenant lane, so pod usage rows attribute
        # to "anonymous" — the same reduced-feature stance as deadlines
        # and SLO classes (metering per tenant wants solo replicas
        # behind the gateway).
        self._reject_deadline(deadline_s)
        self._reject_slo_class(slo_class)
        ticket = self._stage(prompt_tokens, max_new_tokens, temperature,
                             top_p, seed, adapter_id=adapter_id,
                             grammar=grammar, trace=trace)
        return ticket.wait()

    def generate_many(self, prompt_tokens, n, *, max_new_tokens=None,
                      temperature=None, top_p=None, seed=None,
                      adapter_id=None, grammar=None, logprobs=None,
                      slo_class=None, trace=None, tenant=None):
        """OpenAI ``n``/``best_of`` over the pod: stage ``n`` copies with
        derived seeds (same 7919-stride rule as ThreadedEngine.generate_many
        so pod and solo serving replay identically for a given seed), then
        block until all finish. Returns objects with ``.tokens`` and
        ``.lp_token`` — the server's candidate surface."""
        self._reject_slo_class(slo_class)
        if logprobs is not None:
            from ditl_tpu.infer.continuous import BadRequestError

            raise BadRequestError(
                "logprobs do not compose with --pod serving (the tick "
                "broadcast carries token ids only)"
            )
        if seed is None:
            import random as _random

            seed = _random.getrandbits(31)
        tickets: list[_Ticket] = []

        def _abandon_siblings():
            # A failure on copy k must not leave siblings decoding dead
            # budget pod-wide. Still-staged copies are pulled out of
            # self._staged entirely (never broadcast); in-flight ones are
            # flagged so the pump cancels instead of registering them;
            # admitted ones get a real cancel tick.
            with self._cond:
                live = set(id(t) for t in tickets)
                self._staged = [
                    entry for entry in self._staged
                    if id(entry[-1]) not in live
                ]
                for t in tickets:
                    t.abandoned = True
                    if t.req_id is not None and not t.done.is_set():
                        self._cancels.add(t.req_id)
                        self._tickets.pop(t.req_id, None)
                self._cond.notify_all()

        try:
            from ditl_tpu.infer.continuous import derive_copy_seed

            for i in range(n):
                tickets.append(self._stage(
                    prompt_tokens, max_new_tokens, temperature, top_p,
                    derive_copy_seed(seed, i),
                    adapter_id=adapter_id, grammar=grammar, trace=trace,
                ))
            return [_PodResult(t.wait()) for t in tickets]
        except BaseException:
            _abandon_siblings()
            raise

    def stream_one(self, prompt_tokens, *, max_new_tokens=None,
                   temperature=None, top_p=None, seed=None, adapter_id=None,
                   grammar=None, deadline_s=None, slo_class=None, trace=None,
                   tenant=None):
        import queue as _queue

        self._reject_deadline(deadline_s)
        self._reject_slo_class(slo_class)
        stream: _queue.Queue = _queue.Queue()
        # Staged EAGERLY (not on first next()): QueueFullError must raise
        # while the HTTP layer can still answer 429 — after the SSE headers
        # there is no status left to send (ADVICE r2).
        ticket = self._stage(prompt_tokens, max_new_tokens, temperature,
                             top_p, seed, stream=stream,
                             adapter_id=adapter_id, grammar=grammar,
                             trace=trace)

        def chunks():
            try:
                while True:
                    try:
                        chunk = stream.get(timeout=1.0)
                    except _queue.Empty:
                        if self._stop:
                            raise RuntimeError(
                                "pod serving stopped mid-stream"
                            ) from self._error
                        continue
                    if chunk is None:
                        if ticket.error is not None:
                            # fail() uses the same end-of-stream sentinel; a
                            # driver error must not present a truncated
                            # stream as a clean completion.
                            raise RuntimeError(
                                "pod serving stopped mid-stream"
                            ) from ticket.error
                        # The engine enqueues the sentinel inside the tick;
                        # the pump marks the ticket finished moments later
                        # (take_finished). Wait for that so the finally
                        # clause below doesn't broadcast a spurious pod-wide
                        # cancel tick for a cleanly finished request
                        # (ADVICE r2).
                        ticket.done.wait(timeout=2.0)
                        return
                    yield chunk
            finally:
                # Cancel only abandoned/failed streams: a cleanly finished
                # request was already removed by take_finished, and a dead
                # cancel would cost one pointless pod-wide broadcast tick.
                if ticket.req_id is not None and not ticket.done.is_set():
                    self.cancel(ticket.req_id)

        return chunks()

    def cancel(self, req_id: int) -> None:
        with self._cond:
            self._cancels.add(req_id)
            self._cond.notify_all()

    def close(self) -> None:
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        self._pump.join(timeout=600)
        if self._pump.is_alive():
            logger.error("pod continuous pump did not drain within 600s")


class _PodResult:
    """Finished-candidate surface for ``generate_many`` (the server reads
    ``.tokens`` and ``.lp_token``; the tick broadcast carries no logprobs,
    so ``lp_token`` is always None in pod mode)."""

    __slots__ = ("tokens", "lp_token")

    def __init__(self, tokens: list[int]):
        self.tokens = tokens
        self.lp_token = None


class _Ticket:
    """One staged request's handoff between an HTTP thread and the pump."""

    def __init__(self, stream=None, trace=None):
        self.stream = stream
        self.trace = trace  # upstream span context (process-0 spans only)
        self.req_id: int | None = None
        self.result: list[int] | None = None
        self.error: BaseException | None = None
        self.abandoned = False  # generate_many sibling failed mid-stage
        self.done = threading.Event()

    def finish(self, tokens: list[int]) -> None:
        self.result = tokens
        self.done.set()

    def fail(self, err: BaseException) -> None:
        self.error = err
        self.done.set()
        if self.stream is not None:
            self.stream.put(None)

    def wait(self) -> list[int]:
        self.done.wait()
        if self.error is not None:
            raise self.error
        return self.result


def continuous_worker_loop(engine) -> str:
    """Run on every ``jax.process_index() != 0`` process under
    ``--pod --engine continuous``: mirror the coordinator's tick broadcasts
    on an identical engine replica until shutdown. Returns the exit reason
    (``"shutdown"`` | ``"desync"`` | ``"bad-opcode"``) so launchers and the
    multi-process drill can tell a clean teardown from a loud divergence
    halt."""
    engine.freeze_spec_threshold()  # same reason as PodContinuousDriver
    logger.info("pod continuous worker: entering broadcast loop")
    while True:
        header = _broadcast(np.zeros((8,), np.int32))
        op = int(header[0])
        if op == _SHUTDOWN:
            logger.info("pod continuous worker: shutdown")
            return "shutdown"
        if op != _CTICK:
            logger.error("pod continuous worker: unexpected opcode %d", op)
            return "bad-opcode"
        n_sub, ids_total, n_cancel = int(header[1]), int(header[2]), int(header[3])
        meta = (_broadcast(np.zeros((n_sub, 6), np.int32))
                if n_sub else np.zeros((0, 6), np.int32))
        ids = (_broadcast(np.zeros((ids_total,), np.int32))
               if n_sub else np.zeros((0,), np.int32))
        cc = (_broadcast(np.zeros((n_cancel,), np.int32))
              if n_cancel else np.zeros((0,), np.int32))
        ok = True
        try:
            _apply_ctick(engine, meta, ids, cc)
            engine.take_finished()  # drop replicated results
        except Exception:
            ok = False
            logger.exception("pod continuous worker: tick failed")
        if not _status_fingerprints_agree(
            ok, engine.scheduler_fingerprint() if ok else 0
        ):
            logger.error(
                "pod continuous worker: tick status/scheduler-state "
                "diverged; shutting down"
            )
            return "desync"
