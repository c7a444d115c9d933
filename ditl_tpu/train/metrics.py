"""Metrics / observability (SURVEY.md §5).

The reference logs per-example prompt/response/label lines on rank 0 (ref
``src/distributed_inference.py:71-76``). Here the unit of observability is the
train step, and the headline numbers are the BASELINE.json metrics:
**tokens/sec/chip** and **step-time p50**. Device metrics arrive as jax.Arrays;
they are only synced to host at ``log_every`` boundaries so the metric path
never stalls the device pipeline — and that boundary sync is ONE
``jax.device_get`` over every pending step's metrics, not one transfer per
step or per key.

Per-step phase breakdown (ISSUE 3): each flushed JSONL row carries
``data_wait_s`` (host time blocked on the data pipeline, passed in by the
trainer) and ``dispatch_s`` (host wall inside the step call — dispatch is
async, so this is host work, not device time); each flush records its own
blocking-sync wall as ``sync_s`` on the row that triggered it. The summary
totals the three, which is where "where did the wall clock go" starts before
the goodput report (telemetry/goodput.py) finishes it.

The operator's throughput figure: a flush is the only point where the host
has caught up with the device, so the wall from the end of one flush's sync
to the end of the next, over the steps flushed, is the one step time that
holds however far the host runs ahead. It is ``flush_step_s`` on the flushing
row, and what the ``step N:`` log line prints (the first flush's interval
starts when the logger is made and holds the compile). Every row also carries
``compile_count_cum`` / ``compile_s_cum`` / ``compile_miss_count_cum``
(utils/profiling.compile_counter; the last counts the programs the persistent
cache did not hold) as they stood at its flush, and every device metric the
step reports under the step's own name (``train/step.py``: the experts' load
ratio, the flash kernels' block counts).

The FIRST row a process writes carries ``startup`` (``entry_wall`` and the
seconds of every leg of the process's start, telemetry/tracing.py
``StartupRecorder``): the first flush's sync is where the start's last leg,
``first_flush``, ends. A resumed process appends its own.
"""

from __future__ import annotations

import json
import statistics
import time
from typing import Any

from ditl_tpu.annotations import hot_path
from ditl_tpu.runtime.distributed import is_coordinator
from ditl_tpu.utils.logging import get_logger
from ditl_tpu.utils.profiling import compile_counter

logger = get_logger(__name__)

__all__ = ["MetricsLogger"]


class MetricsLogger:
    def __init__(
        self,
        log_every: int = 10,
        n_chips: int | None = None,
        metrics_file: str = "",
        anatomy=None,
        on_host_metrics=None,
        startup=None,
    ):
        """``metrics_file``: optional coordinator-only JSONL scalar stream
        (one object per STEP WINDOW — every pending entry is written at each
        flush, not just the newest; the flush used to drop all interior
        steps of a log_every window, ISSUE 3 satellite) — the
        TensorBoard-scalar equivalent without a TF dependency; any dashboard
        can tail it.

        ``anatomy``: optional ``telemetry.perf.StepAnatomy`` fed from the
        same phase clocks this logger already keeps (ISSUE 7): ``data_wait``
        and ``host_dispatch`` at each end_step, ``device_compute`` at each
        flush sync — the trainer adds the matching wall spans and the
        checkpoint bucket.

        ``on_host_metrics``: optional ``(step, host_dict, step_time_s)``
        callback invoked once per flushed window AFTER the flush's own
        bookkeeping completes (ISSUE 10) — the one place loss/grad_norm
        are already host floats, so the anomaly plane's training
        detectors ride the existing log_every sync and add ZERO blocking
        transfers (tier-1-pinned). A callback exception (the non-finite
        crash) propagates only after the pending queue is cleared, so the
        close() flush never re-syncs.

        ``startup``: the process's ``StartupRecorder``
        (telemetry/tracing.py). The first flush's sync closes its
        ``first_flush`` leg and the recorder itself; the first row written
        carries its ``block()`` as ``startup``."""
        import jax

        self.anatomy = anatomy
        self.on_host_metrics = on_host_metrics
        self._startup = startup  # None once its block is written
        self.log_every = max(1, log_every)
        self.n_chips = n_chips if n_chips is not None else jax.device_count()
        self.step_times: list[float] = []
        self.tokens_per_sec_chip: list[float] = []
        self._last_t: float | None = None
        # When the host last knew the device had caught up: the end of the
        # previous flush's sync (at first, now).
        self._last_sync_t = time.perf_counter()
        self._compiles = compile_counter()
        # (step, metrics, n_steps, dt, data_wait_s) per un-flushed window.
        self._pending: list[tuple[int, Any, int, float | None, float]] = []
        self._metrics_fh = None
        # Phase totals (host wall seconds) across the run.
        self.data_wait_s = 0.0
        self.dispatch_s = 0.0
        self.sync_s = 0.0
        if metrics_file and is_coordinator():
            self._metrics_fh = open(metrics_file, "a", buffering=1)

    @hot_path
    def start_step(self) -> None:
        self._last_t = time.perf_counter()

    @hot_path
    def end_step(
        self, step: int, device_metrics: Any, n_steps: int = 1,
        data_wait_s: float = 0.0, excluded_s: float = 0.0,
    ) -> None:
        """Record wall time; stash device metrics without forcing a sync.
        ``n_steps > 1`` when one call ran a whole compiled step window
        (train/step.make_multi_step): wall time is divided per step, and
        ``device_metrics['n_tokens']`` is expected to cover the window.
        ``data_wait_s``: host time spent waiting on the data pipeline for
        this window (phase breakdown column). ``excluded_s``: wall inside
        the start/end interval that belongs to another accounting bucket
        (the trainer passes its measured profiler work) — subtracted from
        the ANATOMY's host_dispatch feed so conservation against the
        profiler-excluded wall holds; the phase columns keep the historical
        full-interval semantics."""
        now = time.perf_counter()
        dt = None
        if self._last_t is not None:
            dt = (now - self._last_t) / max(1, n_steps)
            self.step_times.append(dt)
            self.dispatch_s += now - self._last_t
            if self.anatomy is not None:
                self.anatomy.add(
                    "host_dispatch", now - self._last_t - excluded_s
                )
        self._last_t = None
        self.data_wait_s += data_wait_s
        if self.anatomy is not None:
            self.anatomy.add("data_wait", data_wait_s)
        self._pending.append(
            (step, device_metrics, max(1, n_steps), dt, data_wait_s)
        )
        if step % self.log_every < n_steps:
            self.flush()

    def flush(self) -> None:
        if not self._pending:
            return
        import jax

        # ONE blocking transfer for every pending window's metrics — the
        # only device sync on the metrics path, and its wall time is the
        # "device-blocked" phase (the host catching up to the async-
        # dispatched step stream).
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("train.flush"):
            host_all = jax.device_get([m for _, m, _, _, _ in self._pending])
        now = time.perf_counter()
        n_flushed = sum(n for _, _, n, _, _ in self._pending)
        startup_block = None
        if self._startup is not None:
            self._startup.mark("first_flush", steps=n_flushed)
            self._startup.close()
            startup_block, self._startup = self._startup.block(), None
        sync_s = now - t0
        self.sync_s += sync_s
        # The flush-to-flush wall over the steps flushed: the only interval
        # that ends in a device sync at both ends.
        flush_wall_s = now - self._last_sync_t
        self._last_sync_t = now
        flush_step_s = flush_wall_s / n_flushed
        flush_tps_chip = (
            sum(float(h.get("n_tokens", 0.0)) for h in host_all)
            / flush_wall_s / self.n_chips
        )
        compiles = self._compiles.snapshot()
        if self.anatomy is not None:
            self.anatomy.add("device_compute", sync_s)
        last_i = len(self._pending) - 1
        flushed: list[tuple[int, dict, float]] = []
        for i, (step, _, n_steps, dt, data_wait_s) in enumerate(self._pending):
            host = {k: float(v) for k, v in host_all[i].items()}
            if dt is None:
                continue
            flushed.append((step, host, dt))
            tps_chip = host.get("n_tokens", 0.0) / (dt * n_steps) / self.n_chips
            self.tokens_per_sec_chip.append(tps_chip)
            if i == last_i and is_coordinator():
                logger.info(
                    "step %d: loss=%.4f grad_norm=%.3f step_time=%.3fs "
                    "tokens/sec/chip=%.1f",
                    step,
                    host.get("loss", float("nan")),
                    host.get("grad_norm", float("nan")),
                    flush_step_s,
                    flush_tps_chip,
                )
            if self._metrics_fh is not None:
                row = {
                    "step": step,
                    "step_time_s": round(dt, 6),
                    "tokens_per_sec_per_chip": round(tps_chip, 2),
                    "data_wait_s": round(data_wait_s, 6),
                    "dispatch_s": round(dt * n_steps, 6),
                    "compile_count_cum": compiles["compile_count"],
                    "compile_s_cum": compiles["compile_s"],
                    "compile_miss_count_cum": compiles["cache_miss_count"],
                    **{k: round(v, 6) for k, v in host.items()},
                }
                if startup_block is not None:
                    row["startup"], startup_block = startup_block, None
                if i == last_i:
                    # The sync belongs to the flush, not any single step;
                    # carried on the row that triggered it.
                    row["sync_s"] = round(sync_s, 6)
                    row["flush_step_s"] = round(flush_step_s, 6)
                self._metrics_fh.write(json.dumps(row, sort_keys=True) + "\n")
        self._pending.clear()
        if self.on_host_metrics is not None:
            # After clear(): a callback that raises (the non-finite-loss
            # crash, ISSUE 10) must not leave pending rows for close() to
            # re-flush — that would add a second blocking transfer.
            for step, host, dt in flushed:
                self.on_host_metrics(step, host, dt)

    def close(self) -> None:
        self.flush()
        if self._metrics_fh is not None:
            self._metrics_fh.close()
            self._metrics_fh = None

    def phase_totals(self) -> dict[str, float]:
        """Cumulative host-wall phase breakdown: data-wait / host dispatch /
        device-blocked (flush sync)."""
        return {
            "data_wait_s": round(self.data_wait_s, 6),
            "dispatch_s": round(self.dispatch_s, 6),
            "device_blocked_s": round(self.sync_s, 6),
        }

    def summary(self) -> dict[str, float]:
        """The headline numbers. p50 over steps after compile warm-up."""
        times = self.step_times[1:] if len(self.step_times) > 1 else self.step_times
        tps = self.tokens_per_sec_chip[1:] if len(self.tokens_per_sec_chip) > 1 else self.tokens_per_sec_chip
        out: dict[str, float] = {}
        if times:
            out["step_time_p50_s"] = statistics.median(times)
        if tps:
            out["tokens_per_sec_per_chip_p50"] = statistics.median(tps)
        out.update({f"phase_{k}": v for k, v in self.phase_totals().items()})
        return out

    def summary_json(self) -> str:
        return json.dumps(self.summary(), sort_keys=True)
