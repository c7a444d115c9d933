"""Training/hardware performance observatory (ISSUE 7 tentpole leg 1+3).

Three pieces, all host-only like the rest of telemetry/ (nothing here
imports jax at module scope; the compiled-cost helpers take the objects the
caller already holds):

- **Step-time anatomy** (``StepAnatomy``): decomposes the training step
  path's wall clock into the phases the trainer can actually measure —
  ``data_wait`` (host blocked in the data iterator), ``host_dispatch``
  (host wall inside the async step call: argument staging, dispatch, and —
  because donated input buffers backpressure the dispatch — any device time
  the host caught up to there), ``device_compute`` (host wall blocked in
  the metrics flush sync, i.e. the device finishing work the host had
  already dispatched), and ``checkpoint_overlap`` (the blocking portion of
  async checkpoint saves that interleaves the step stream). Conservation is
  the same design invariant as goodput.py: buckets + the measured ``other``
  remainder equal the tracked wall EXACTLY by construction, and the tier-1
  test asserts the attributed buckets land within 5% of the wall the
  trainer measured independently.

- **Compiled-function cost analysis** (``compiled_cost``, ``roofline``):
  pulls XLA's own flops / bytes-accessed numbers from
  ``jitted.lower(...).compile().cost_analysis()`` and turns them into an
  achieved-vs-roofline report: arithmetic intensity (flops/byte), the
  roofline's MFU ceiling at that intensity, and whether the program sits on
  the compute or memory side of the ridge. This is the per-step complement
  to an analytic end-of-run MFU scalar.

- **Versioned sweep records** (``new_sweep_record`` / ``load_sweep_record``
  / ``record_sweep_cell``): the one JSON format every grid-shaped
  measurement writes (``run_recorded_cells``), so ``perf_compare`` can diff
  any two of them. Records are **resumable**: one file holds a ``cells`` map
  keyed by the cell's override spec; a crashed sweep reruns only the missing
  cells.
"""

from __future__ import annotations

import json
import os
import subprocess
from typing import Any, Mapping

__all__ = [
    "ANATOMY_BUCKETS",
    "SWEEP_SCHEMA",
    "StepAnatomy",
    "compiled_cost",
    "roofline",
    "git_rev",
    "cell_key",
    "new_sweep_record",
    "load_sweep_record",
    "record_sweep_cell",
    "pop_out_arg",
    "run_recorded_cells",
]

# Canonical anatomy bucket names (report keys are f"{name}_s"); "other" is
# computed as the remainder, never added.
ANATOMY_BUCKETS = (
    "data_wait",
    "host_dispatch",
    "device_compute",
    "checkpoint_overlap",
)

# Version stamped into every bench/sweep record. Bump when a field changes
# meaning; perf_compare refuses to diff across schema versions.
SWEEP_SCHEMA = 1


class StepAnatomy:
    """Accumulate the step path's wall-time decomposition.

    The caller owns two clocks: per-bucket host walls (``add``) and the
    independently measured step-path wall (``add_wall``) the buckets are
    conserved against. The two must cover the SAME interval set — the
    trainer adds one wall span per step window (data wait + window body)
    and one per checkpoint save, and feeds the buckets from the phase
    columns train/metrics.py already measures.
    """

    def __init__(self):
        self._buckets: dict[str, float] = {}
        self._wall = 0.0
        self.steps = 0

    def add(self, bucket: str, seconds: float) -> None:
        if bucket not in ANATOMY_BUCKETS:
            raise ValueError(
                f"unknown anatomy bucket {bucket!r} (one of {ANATOMY_BUCKETS})"
            )
        if seconds > 0:
            self._buckets[bucket] = self._buckets.get(bucket, 0.0) + seconds

    def add_wall(self, seconds: float, n_steps: int = 0) -> None:
        """One independently measured step-path wall span (the interval the
        buckets above decompose)."""
        if seconds > 0:
            self._wall += seconds
        self.steps += n_steps

    @property
    def wall_s(self) -> float:
        return self._wall

    def report(self) -> dict:
        """Keys: ``wall_step_s`` (measured), one ``{bucket}_s`` per
        non-empty bucket, ``other_s`` (floored remainder),
        ``conservation_error`` (signed attributed-vs-wall mismatch as a
        fraction of wall — the number the 5% tier-1 invariant pins),
        per-step means when ``steps`` is known, and ``steps``."""
        out: dict = {"wall_step_s": round(self._wall, 6), "steps": self.steps}
        tracked = sum(self._buckets.values())
        for name in ANATOMY_BUCKETS:
            if name in self._buckets:
                out[f"{name}_s"] = round(self._buckets[name], 6)
        out["other_s"] = round(max(0.0, self._wall - tracked), 6)
        if self._wall > 0:
            out["conservation_error"] = round(
                (tracked - self._wall) / self._wall, 4
            )
            if self.steps > 0:
                out["per_step_ms"] = {
                    name: round(v / self.steps * 1e3, 3)
                    for name, v in sorted(self._buckets.items())
                }
                out["per_step_ms"]["wall"] = round(
                    self._wall / self.steps * 1e3, 3
                )
        return out


def compiled_cost(compiled: Any, n_steps: int = 1) -> dict | None:
    """Flops + bytes accessed of a compiled XLA executable, per step.

    ``compiled`` is what ``jitted.lower(*args).compile()`` returns;
    ``n_steps`` divides the program's totals when one program runs a whole
    step window (train/step.make_multi_step). Returns None when the backend
    exposes no cost model (some plugin runtimes) — callers omit the
    roofline rather than guessing. Never raises: cost analysis is advisory
    telemetry, not a correctness dependency."""
    try:
        ca = compiled.cost_analysis()
    except Exception:  # noqa: BLE001 - backend-dependent, advisory only
        return None
    if not isinstance(ca, Mapping):
        return None
    flops = float(ca.get("flops", 0.0))
    byts = float(ca.get("bytes accessed", 0.0))
    if flops <= 0:
        return None
    out = {
        "flops_per_step": flops / max(1, n_steps),
        "bytes_per_step": byts / max(1, n_steps) if byts > 0 else None,
    }
    try:
        mem = compiled.memory_analysis()
        out["temp_bytes"] = int(mem.temp_size_in_bytes)
        out["argument_bytes"] = int(mem.argument_size_in_bytes)
        out["output_bytes"] = int(mem.output_size_in_bytes)
    except Exception:  # noqa: BLE001
        pass
    return out


def roofline(
    flops_per_step: float,
    bytes_per_step: float | None,
    step_time_s: float,
    peak_flops: float,
    peak_bw: float | None,
) -> dict:
    """Achieved-vs-roofline report for one compiled step.

    ``mfu_cost`` is XLA-counted flops / wall / peak — the cost-model
    counterpart to bench's analytic MFU (it INCLUDES remat recompute, so
    ``mfu_cost - mfu`` measures the recompute tax). ``ai_flops_per_byte``
    is arithmetic intensity; when the bandwidth peak is known the roofline
    ceiling at that intensity is ``min(1, ai * peak_bw / peak_flops)`` and
    ``bound`` names which side of the ridge the program sits on."""
    out: dict = {
        "flops_per_step": flops_per_step,
        "achieved_tflops": round(flops_per_step / step_time_s / 1e12, 3),
        "mfu_cost": round(flops_per_step / step_time_s / peak_flops, 4),
    }
    if bytes_per_step:
        ai = flops_per_step / bytes_per_step
        out["bytes_per_step"] = bytes_per_step
        out["ai_flops_per_byte"] = round(ai, 2)
        out["achieved_gbps"] = round(bytes_per_step / step_time_s / 1e9, 2)
        if peak_bw:
            ridge = peak_flops / peak_bw
            out["roofline_mfu_cap"] = round(min(1.0, ai / ridge), 4)
            out["bound"] = "memory" if ai < ridge else "compute"
            out["hbm_utilization"] = round(
                bytes_per_step / step_time_s / peak_bw, 4
            )
    return out


def git_rev(repo_dir: str | None = None) -> str:
    """Short git revision of the repo a record was measured at (plus
    ``-dirty`` when the tree has local edits); "unknown" outside a repo —
    records stay writable anywhere."""
    cwd = repo_dir or os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    try:
        rev = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], cwd=cwd,
            capture_output=True, text=True, timeout=10,
        )
        if rev.returncode != 0:
            return "unknown"
        dirty = subprocess.run(
            ["git", "status", "--porcelain"], cwd=cwd,
            capture_output=True, text=True, timeout=10,
        )
        suffix = "-dirty" if dirty.returncode == 0 and dirty.stdout.strip() else ""
        return rev.stdout.strip() + suffix
    except (OSError, subprocess.SubprocessError):
        return "unknown"


def cell_key(overrides: Mapping[str, Any]) -> str:
    """Deterministic key for one sweep cell: sorted ``k=v`` joined with
    commas (`"(base)"` for the empty cell) — human-greppable in the JSON
    and stable across runs, which is what resumability hangs on."""
    if not overrides:
        return "(base)"
    return ",".join(f"{k}={overrides[k]}" for k in sorted(overrides))


def new_sweep_record(name: str, meta: Mapping[str, Any] | None = None) -> dict:
    return {
        "schema": SWEEP_SCHEMA,
        "git_rev": git_rev(),
        "sweep": name,
        "meta": dict(meta or {}),
        "cells": {},
    }


def load_sweep_record(path: str) -> dict | None:
    """Load an existing sweep record for resumption; None when the file is
    absent, unparseable, or a different schema version (a stale-format file
    is rewritten from scratch rather than appended to incompatibly)."""
    try:
        with open(path) as f:
            rec = json.load(f)
    except (OSError, ValueError):
        return None
    if not isinstance(rec, dict) or rec.get("schema") != SWEEP_SCHEMA:
        return None
    if not isinstance(rec.get("cells"), dict):
        return None
    return rec


def record_sweep_cell(
    path: str, record: dict, key: str, cell: Mapping[str, Any]
) -> dict:
    """Add one finished cell and persist the whole record atomically
    (tmp + rename): a sweep killed mid-write resumes from the last
    complete cell set, never from a torn JSON."""
    record["cells"][key] = dict(cell)
    tmp = f"{path}.tmp"
    with open(tmp, "w") as f:
        json.dump(record, f, indent=2, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    return record


def pop_out_arg(args: list, default: str) -> str:
    """Extract a ``--out=PATH`` flag from a positional argv list (mutates
    ``args``) — the experiment scripts' shared spelling."""
    out = default
    for a in list(args):
        if a.startswith("--out="):
            out = a.split("=", 1)[1]
            args.remove(a)
    return out


def run_recorded_cells(path, name, meta, items, runner) -> dict:
    """Record-as-you-go loop for A/B and grid measurements: each
    ``(key, payload)`` item runs through ``runner(key, payload) -> cell
    dict`` and lands in the sweep record at ``path`` immediately (atomic
    write per cell). Resume semantics: cells already recorded WITHOUT an
    error are skipped, errored cells are retried (a transient failure must
    not be permanently skipped), a runner returning an ``{"error": ...}``
    cell records the failure so perf_compare's measured-to-crashing gate
    sees it, and a record measured under a different base configuration
    (``meta``) is refused. Returns ``{key: cell}`` covering both freshly
    run and resumed cells."""
    record = load_sweep_record(path)
    if record is None:
        record = new_sweep_record(name, meta=meta)
    elif record.get("meta", {}) != dict(meta or {}):
        # Cell keys name only the swept knobs, so resuming would reuse the
        # other configuration's numbers — and feed perf_compare
        # wrong-config baselines.
        raise ValueError(
            f"{path} was recorded under a different base config "
            f"({record.get('meta')} != {dict(meta or {})}); point the "
            "record elsewhere or delete the stale one")
    out: dict = {}
    for key, payload in items:
        prior = record["cells"].get(key)
        if prior is not None and "error" not in prior:
            out[key] = prior
            print(f"[{key}] already recorded in {path} — skipping",
                  flush=True)
            continue
        cell = runner(key, payload)
        if cell is None:
            continue
        record = record_sweep_cell(path, record, key, cell)
        out[key] = cell
    return out
