"""Tracing / profiling (SURVEY.md §5: absent in the reference, whose
monitoring story is 'check console output' + nvidia-smi, ref
``docs/setup_guide.md:68-71``).

Two capture mechanisms, both process-0-gated and off by default:

- ``start_profiler_server(port)`` (here; the trainer's
  ``runtime.profiler_port`` and the server's ``--profiler-port``) — live
  capture from TensorBoard/XProf.
- ``StepProfiler`` (here) — programmatic capture of a step window
  [``profile_start_step``, ``profile_start_step + profile_num_steps``) to
  ``profile_dir``, viewable in TensorBoard. Capturing a *window* (not the
  whole run) keeps trace files bounded and skips the untypical compile step.

Whoever starts a trace, every dispatched step is wrapped in a
``StepTraceAnnotation`` (``annotate_step``), so the trace has step marks on
its own clock. Outside a trace an annotation costs microseconds.

``compile_counter()`` counts compilations where they happen, through
``jax.monitoring``: the rows of ``metrics_file`` and the server's
``/v1/stats`` carry its totals, the programs the persistent cache did not
hold among them.
"""

from __future__ import annotations

import threading

import jax

from ditl_tpu.utils.logging import get_logger

logger = get_logger(__name__)

__all__ = [
    "CompileCounter",
    "StepProfiler",
    "annotate_step",
    "compile_counter",
    "start_profiler_server",
]

# jax 0.9.0 (jax/_src/dispatch.py): lowering to an MLIR module, and the
# backend's compile; the latter's interval includes a hit's retrieval from
# the persistent cache. Tracing (``jaxpr_trace_duration``) is left out: a
# nested jit's trace is timed inside its caller's as well, so a sum over
# events would count it twice.
_LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# jax/_src/compiler.py ``compile_or_get_cached``: recorded on a hit alone,
# INSIDE the backend-compile interval, so on the thread that builds the
# program it arrives just before that program's backend-compile event.
_CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


class CompileCounter:
    """Programs built by this process (compiled, or loaded from the
    persistent cache) and the seconds that took, cumulative, and how many of
    them the persistent cache did not hold. ``journal``
    (telemetry/journal.py), when set, gets one ``jit.compile`` event per
    program with its name, its seconds and ``cache``: ``hit`` (with
    ``retrieval_s``), ``miss`` or ``off`` (no persistent cache): which step
    recompiled, and whether a slow start compiled or loaded. ``notes``
    maps a program's name to further fields of its event: facts fixed when
    the program was built (the trainer's ``loss_partition``)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.compile_count = 0  # guarded-by: _lock
        self.compile_s = 0.0  # guarded-by: _lock
        self.cache_miss_count = 0  # guarded-by: _lock
        self.journal = None
        self.notes: dict[str, dict] = {}
        # A hit's retrieval seconds, until its thread's backend-compile event.
        self._hit = threading.local()

    def on_duration(self, event: str, duration_s: float, **kw) -> None:
        if event == _LOWERING_EVENT:
            with self._lock:
                self.compile_s += duration_s
        elif event == _CACHE_RETRIEVAL_EVENT:
            self._hit.retrieval_s = duration_s
        elif event == _BACKEND_COMPILE_EVENT:
            name = str(kw.get("fun_name", ""))
            retrieval_s = getattr(self._hit, "retrieval_s", None)
            self._hit.retrieval_s = None
            if retrieval_s is not None:
                cache = {"cache": "hit", "retrieval_s": round(retrieval_s, 6)}
            elif (jax.config.jax_compilation_cache_dir
                    and jax.config.jax_enable_compilation_cache):
                cache = {"cache": "miss"}
            else:
                cache = {"cache": "off"}
            with self._lock:
                self.compile_count += 1
                self.compile_s += duration_s
                self.cache_miss_count += cache["cache"] == "miss"
            journal = self.journal
            if journal is not None:
                journal.event("jit.compile", program=name,
                              compile_s=round(duration_s, 6), **cache,
                              **self.notes.get(name, {}))

    def snapshot(self) -> dict:
        with self._lock:
            return {"compile_count": self.compile_count,
                    "compile_s": round(self.compile_s, 6),
                    "cache_miss_count": self.cache_miss_count}


_counter: CompileCounter | None = None
_counter_lock = threading.Lock()


def compile_counter() -> CompileCounter:
    """The process's one counter; the first call registers its listener
    (``jax.monitoring`` listeners are per process and cannot be scoped)."""
    global _counter
    with _counter_lock:
        if _counter is None:
            _counter = CompileCounter()
            jax.monitoring.register_event_duration_secs_listener(
                _counter.on_duration
            )
        return _counter


def start_profiler_server(port: int) -> None:
    """``jax.profiler.start_server`` on process 0 when ``port`` > 0: the one
    switch both programs have for a device trace taken from outside."""
    if port > 0 and jax.process_index() == 0:
        jax.profiler.start_server(port)
        logger.info("jax.profiler server on port %d", port)


def annotate_step(step: int):
    """Context manager naming this step in the trace timeline."""
    return jax.profiler.StepTraceAnnotation("train_step", step_num=step)


class StepProfiler:
    """Captures steps [start, start+num) to ``directory`` on process 0.

    Usage (trainer loop):
        prof.maybe_start(global_step)
        with prof.annotate(global_step):
            state, metrics = train_step(state, batch)
        prof.maybe_stop(global_step, metrics)

    ``tracer`` (telemetry/tracing.py, ISSUE 6 satellite): when armed, the
    capture window is recorded as a ``profiler.capture`` span in the
    training journal — the xprof window shows up ON the merged timeline
    (with its step range and output dir) instead of existing only as a
    goodput bucket.
    """

    def __init__(self, directory: str, start_step: int, num_steps: int = 3,
                 tracer=None):
        self.directory = directory
        self.start_step = start_step
        self.num_steps = num_steps
        self._active = False
        self._done = False
        self._stop_after = start_step + num_steps - 1
        self._enabled = bool(directory) and num_steps > 0 and jax.process_index() == 0
        self._tracer = tracer
        self._span_t0 = 0.0
        self._window_start = 0

    def maybe_start(self, step: int) -> None:
        # >= not ==: a resumed run whose restored step is already past
        # start_step still gets its window (shifted to the resume point).
        if self._enabled and not self._active and not self._done and step >= self.start_step:
            import time as _time

            jax.profiler.start_trace(self.directory)
            self._active = True
            self._stop_after = step + self.num_steps - 1
            self._span_t0 = _time.time()
            self._window_start = step
            logger.info(
                "profiler: tracing steps %d..%d to %s",
                step, self._stop_after, self.directory,
            )

    def _trace_bytes(self) -> int:
        """Total bytes of trace artifacts under ``directory`` — the size of
        what this capture wrote to disk (xplane.pb + json sidecars)."""
        import os

        total = 0
        try:
            for root, _dirs, files in os.walk(self.directory):
                for name in files:
                    try:
                        total += os.path.getsize(os.path.join(root, name))
                    except OSError:
                        continue
        except OSError:
            pass
        return total

    def _record_span(self, last_step: int, partial: bool) -> None:
        """ISSUE 7 satellite: the span carries the capture's measured wall
        (``capture_s`` — start_trace through the trace write; the goodput
        ``profiler`` bucket the trainer tracks covers the same interval, so
        the overhead is attributable instead of vanishing into ``other``)
        and the on-disk trace size (``trace_bytes``)."""
        if self._tracer is None or not getattr(self._tracer, "armed", False):
            return
        import time as _time

        self._tracer.start_span(
            "profiler.capture", t0=self._span_t0,
            start_step=self._window_start, last_step=last_step,
            directory=self.directory, partial=partial,
            capture_s=round(_time.time() - self._span_t0, 6),
            trace_bytes=self._trace_bytes(),
        ).end()

    def annotate(self, step: int):
        """The step's mark, inside this profiler's window or not: a trace
        started by anyone else (``start_profiler_server``, a benchmark's
        launcher thread) gets step marks too."""
        return annotate_step(step)

    def maybe_stop(self, step: int, wait_for=None) -> None:
        """``step`` is the LAST dispatched step since ``maybe_start`` — with
        step windows (train.steps_per_call > 1) the caller passes the window's
        last step, so the trace covers whole windows (rounding the configured
        step count up to a window boundary, never running a full extra
        window). ``wait_for``: arrays that step produced (its metrics, or the
        state); the trace stops only when they are ready, so that it holds
        the device time of the steps it names. ``jax.effects_barrier()``
        does not wait for the device: a trace stopped after it held 5 ms of
        three 1.45 s steps (PERF.md, PR 22)."""
        if self._active and step >= self._stop_after:
            jax.block_until_ready(wait_for)
            jax.profiler.stop_trace()
            self._active = False
            self._done = True
            self._record_span(step, partial=False)
            logger.info("profiler: trace written to %s", self.directory)

    def close(self, wait_for=None) -> None:
        """Mirror ``maybe_stop`` for a trainer exiting mid-window (epoch end,
        exception, total_steps inside the window): wait for ``wait_for``
        first so the trace still contains the device timeline of the steps
        that DID run, and mark ``_done`` so a reused profiler cannot restart
        a second window after its trace was finalized (ISSUE 3 satellite)."""
        if self._active:
            # The trainer calls this in its ``finally``: a step that failed
            # on the device raises here again, and must neither leave the
            # trace open nor hide the first error or the clean-up after it.
            try:
                jax.block_until_ready(wait_for)
            except Exception as e:
                logger.warning("profiler: the last step did not finish (%r); "
                               "the trace may lack its device time", e)
            finally:
                jax.profiler.stop_trace()
                self._active = False
                self._done = True
            self._record_span(self._stop_after, partial=True)
            logger.info("profiler: trace (partial window) written to %s",
                        self.directory)
