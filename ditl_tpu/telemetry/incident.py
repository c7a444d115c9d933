"""Self-contained incident bundles (ISSUE 10 tentpole leg c).

When a detector (telemetry/anomaly.py) fires, the evidence an operator
needs is scattered across process state that is about to be lost: the
flight rings' last few hundred rows, the current /metrics exposition, the
journal tail, the trace records of the affected window. The
:class:`IncidentManager` freezes all of it into ONE directory — the
"diagnosable from one artifact" contract — with the production hygiene a
black box needs:

- **fingerprint dedupe + cooldown**: the same anomaly kind maps to the
  same fingerprint; within ``cooldown_s`` of a bundle for that
  fingerprint, further triggers only bump the suppressed counter. A
  sustained deadline storm produces exactly one bundle, not one per
  detector window (tier-1-pinned).
- **atomic assembly**: every bundle is written into a hidden
  ``.tmp-*`` directory and ``os.rename``d into place whole, so a SIGKILL
  mid-dump never leaves a torn bundle ``--list`` chokes on; stale tmp
  dirs from a killed dump are swept on the next manager construction
  (drilled with a chaos kill at the ``incident.dump`` seam).
- **bounded on disk**: bundles are count-capped and size-capped with
  oldest-first GC, the journal-rotation spirit applied to incident dirs.
- **attributable**: the manifest stamps schema versions, git revision,
  the config snapshot, and — when the chaos plane is armed and has fired —
  the ``injected_fault`` summary, closing the loop between the fault
  plane and the diagnosis plane (a chaos-injected storm reads as such,
  not as an organic mystery).

Bundle layout::

    incident-<utc>-<seq>-<kind>-<fingerprint>/
      incident.json        manifest (trigger, evidence, stamps, file list)
      flight/<ring>.jsonl  flight-ring dumps (telemetry/flight.py)
      metrics.prom         /metrics snapshot at trigger time
      journal_tail.jsonl   last-N merged journal events
      trace_slice.json     Chrome-trace JSON of the affected window
      memwatch.json        HBM top-k (only when a watcher is armed)

Inspect from the CLI (stdlib-only, jax-free like everything here)::

    python -m ditl_tpu.telemetry.incident --dir DIR [--list | --show NAME]

Counters (``ditl_incidents_total``, ``ditl_incidents_suppressed_total``,
``ditl_incidents_trigger_<kind>_total``) land in the caller's registry so
/metrics answers "did anything fire" without listing directories.
"""

from __future__ import annotations

import contextlib
import json
import os
import re
import shutil
import threading
import time

from ditl_tpu.telemetry.anomaly import Anomaly
from ditl_tpu.telemetry.flight import FLIGHT_SCHEMA, FlightRecorder
from ditl_tpu.utils.logging import get_logger

logger = get_logger(__name__)

__all__ = [
    "INCIDENT_SCHEMA",
    "MANIFEST_NAME",
    "IncidentManager",
    "incidents_total",
    "list_bundles",
    "main",
    "read_bundle",
]

INCIDENT_SCHEMA = 1
MANIFEST_NAME = "incident.json"
_BUNDLE_PREFIX = "incident-"
_TMP_PREFIX = ".tmp-"

_SLUG_RE = re.compile(r"[^a-z0-9_]+")


def _slug(s: str) -> str:
    return _SLUG_RE.sub("_", s.lower()).strip("_") or "unknown"


def _git_rev() -> str:
    """Best-effort HEAD revision (cached): bundles from a fleet must say
    what code produced them; absence (no git, no binary) is recorded as
    "unknown", never an error."""
    global _GIT_REV
    if _GIT_REV is None:
        import subprocess

        rev = "unknown"
        try:
            out = subprocess.run(
                ["git", "rev-parse", "HEAD"],
                cwd=os.path.dirname(os.path.abspath(__file__)),
                capture_output=True, text=True, timeout=5,
            )
            if out.returncode == 0:
                rev = out.stdout.strip()
        except (OSError, subprocess.SubprocessError):
            pass
        _GIT_REV = rev
    return _GIT_REV


_GIT_REV: str | None = None

# Process-lifetime bundle count (a plain int, NOT a list of manager
# references — pinning every per-run manager would leak their config
# snapshots and rings for process lifetime), so a run can report ONE
# "incidents this run" count without plumbing managers through fleet
# factories (chaos/plane.py's injected_summary pattern): capture the
# value at run start and report the delta, so runs sharing a process
# never inherit each other's incidents.
_CREATED_TOTAL = 0


def incidents_total() -> int:
    """Bundles assembled by every manager in this process — the number a
    bench row embeds as a run-start delta (0 when no manager was armed,
    so healthy baselines still carry the key for the perf_compare
    gate)."""
    return _CREATED_TOTAL


def _dir_bytes(path: str) -> int:
    total = 0
    for root, _dirs, files in os.walk(path):
        for f in files:
            with contextlib.suppress(OSError):
                total += os.path.getsize(os.path.join(root, f))
    return total


class IncidentManager:
    """Assembles fingerprint-deduped, cooldown-rate-limited, size/count-
    capped incident bundles for ONE process. Thread-safe: detectors fire
    from the engine driver, HTTP handlers, and supervisor threads."""

    def __init__(
        self,
        directory: str,
        *,
        flight: FlightRecorder | None = None,
        metrics_render=None,
        journal_dir: str = "",
        registry=None,
        config_snapshot: dict | None = None,
        memwatch_dump=None,
        source: str = "",
        cooldown_s: float = 300.0,
        max_bundles: int = 16,
        max_total_mb: float = 64.0,
        journal_tail: int = 200,
        trace_window_s: float = 30.0,
    ):
        if not directory:
            raise ValueError("IncidentManager needs a directory")
        self.directory = directory
        os.makedirs(directory, exist_ok=True)
        self.flight = flight
        self.metrics_render = metrics_render
        self.journal_dir = journal_dir
        self.registry = registry
        self.config_snapshot = config_snapshot
        self.memwatch_dump = memwatch_dump
        self.source = source or f"pid-{os.getpid()}"
        self.cooldown_s = cooldown_s
        self.max_bundles = max(1, max_bundles)
        self.max_total_bytes = int(max_total_mb * 1048576)
        self.journal_tail = max(0, journal_tail)
        self.trace_window_s = trace_window_s
        self.created = 0
        self.suppressed_total = 0  # lifetime, never reset (endpoint-read)
        self.paths: list[str] = []
        self._lock = threading.Lock()
        self._last_fire: dict[str, float] = {}  # guarded-by: _lock
        self._suppressed: dict[str, int] = {}  # guarded-by: _lock
        self._seq = 0  # guarded-by: _lock
        if registry is not None:
            self._total = registry.counter(
                "ditl_incidents", "incident bundles assembled")
            self._suppressed_c = registry.counter(
                "ditl_incidents_suppressed",
                "anomaly triggers deduped/cooled down without a bundle")
        else:
            self._total = self._suppressed_c = None
        # Sweep torn tmp dirs a killed dump left behind (the atomic-rename
        # contract's other half): they are invisible to --list already
        # (hidden names), and deleting them keeps the size cap honest.
        # Tmp names carry the writer's pid: a dir whose owner is STILL
        # ALIVE is a peer's in-progress dump (pod workers may share a
        # directory), never swept.
        for name in os.listdir(directory):
            if not name.startswith(_TMP_PREFIX):
                continue
            try:
                owner = int(name[len(_TMP_PREFIX):].split("-", 1)[0])
            except ValueError:
                owner = 0
            if owner and owner != os.getpid():
                try:
                    os.kill(owner, 0)  # signal 0: existence check only
                    continue  # owner alive: an in-progress dump
                except OSError:
                    pass
            with contextlib.suppress(OSError):
                shutil.rmtree(os.path.join(directory, name))

    # -- trigger -----------------------------------------------------------

    def trigger(self, anomaly: Anomaly) -> str | None:
        """Assemble a bundle for ``anomaly`` unless its fingerprint is in
        cooldown. Returns the bundle path, or None when suppressed. Never
        raises — a failed dump is logged and counted, not propagated into
        the loop that detected the anomaly."""
        fp = anomaly.fingerprint()
        with self._lock:
            last = self._last_fire.get(fp)
            if last is not None and anomaly.ts - last < self.cooldown_s:
                self._suppressed[fp] = self._suppressed.get(fp, 0) + 1
                self.suppressed_total += 1
                if self._suppressed_c is not None:
                    self._suppressed_c.inc()
                return None
            self._last_fire[fp] = anomaly.ts
            suppressed_prior = self._suppressed.pop(fp, 0)
            self._seq += 1
            seq = self._seq
        try:
            path = self._assemble(anomaly, fp, seq, suppressed_prior)
        except Exception:  # noqa: BLE001 - diagnosis must not crash work
            logger.exception("incident: bundle assembly failed for %s",
                             anomaly.kind)
            # Roll the cooldown stamp back: a FAILED dump must not burn
            # the window — the next trigger for this fingerprint retries
            # instead of being suppressed against a bundle that does not
            # exist.
            with self._lock:
                if last is None:
                    self._last_fire.pop(fp, None)
                else:
                    self._last_fire[fp] = last
                if suppressed_prior:
                    self._suppressed[fp] = (
                        self._suppressed.get(fp, 0) + suppressed_prior
                    )
            return None
        global _CREATED_TOTAL
        with self._lock:
            self.created += 1
            _CREATED_TOTAL += 1
            self.paths.append(path)
        if self._total is not None:
            self._total.inc()
            if self.registry is not None:
                self.registry.counter(
                    f"ditl_incidents_trigger_{_slug(anomaly.kind)}",
                    f"incident bundles triggered by {anomaly.kind}",
                ).inc()
        logger.warning("incident: %s -> %s", anomaly.kind, path)
        self._gc()
        return path

    # -- assembly ----------------------------------------------------------

    def _assemble(self, anomaly: Anomaly, fp: str, seq: int,
                  suppressed_prior: int) -> str:
        stamp = time.strftime("%Y%m%dT%H%M%S", time.gmtime(anomaly.ts))
        # The pid keeps names unique when several processes share one
        # directory (pod workers firing the same replicated anomaly in the
        # same second would otherwise collide on the publishing rename);
        # the timestamp prefix keeps the oldest-first GC sort chronological.
        name = (f"{_BUNDLE_PREFIX}{stamp}-{os.getpid()}-{seq:03d}-"
                f"{_slug(anomaly.kind)}-{fp}")
        tmp = os.path.join(self.directory, f"{_TMP_PREFIX}{os.getpid()}-{seq}")
        os.makedirs(tmp, exist_ok=True)
        files: list[str] = []

        def write_json(rel: str, obj) -> None:
            p = os.path.join(tmp, rel)
            os.makedirs(os.path.dirname(p) or tmp, exist_ok=True)
            with open(p, "w") as f:
                json.dump(obj, f, indent=2, sort_keys=True, default=str)
            files.append(rel)

        # Flight rings: one JSONL per ring, rows oldest-first.
        if self.flight is not None:
            for ring_name, rows in self.flight.dump_all().items():
                rel = os.path.join("flight", f"{ring_name}.jsonl")
                p = os.path.join(tmp, rel)
                os.makedirs(os.path.dirname(p), exist_ok=True)
                with open(p, "w") as f:
                    for row in rows:
                        f.write(json.dumps(row, sort_keys=True,
                                           default=str) + "\n")
                files.append(rel)
        # /metrics snapshot at trigger time.
        if self.metrics_render is not None:
            with contextlib.suppress(Exception):
                body = self.metrics_render()
                with open(os.path.join(tmp, "metrics.prom"), "w") as f:
                    f.write(body if body.endswith("\n") else body + "\n")
                files.append("metrics.prom")
        # Journal tail + trace slice of the affected window.
        if self.journal_dir:
            from ditl_tpu.telemetry.journal import merge_journals
            from ditl_tpu.telemetry.trace_export import to_chrome_trace

            records = merge_journals(self.journal_dir)
            if self.journal_tail:
                tail = records[-self.journal_tail:]
                with open(os.path.join(tmp, "journal_tail.jsonl"), "w") as f:
                    for rec in tail:
                        f.write(json.dumps(rec, sort_keys=True) + "\n")
                files.append("journal_tail.jsonl")
            lo = anomaly.ts - self.trace_window_s
            hi = anomaly.ts + 1.0
            window = [r for r in records if lo <= r.get("ts", 0.0) <= hi]
            write_json("trace_slice.json", to_chrome_trace(window))
        # HBM top-k, when a watcher is armed (training leg).
        if self.memwatch_dump is not None:
            with contextlib.suppress(Exception):
                dump = self.memwatch_dump()
                if dump:
                    write_json("memwatch.json", dump)
        # Collapsed-stack profile (ISSUE 18): when a sampling profiler is
        # armed in this process, the bundle carries what every thread was
        # running around the trigger — the "what code was it" evidence
        # next to the "what happened" rings.
        with contextlib.suppress(Exception):
            from ditl_tpu.telemetry.prof import active_profiler

            prof = active_profiler()
            if prof is not None:
                text = prof.collapsed()
                if text:
                    with open(os.path.join(tmp, "profile.txt"), "w") as f:
                        f.write(text if text.endswith("\n") else text + "\n")
                    files.append("profile.txt")
        # Chaos attribution: when the fault plane is armed AND has fired,
        # the injected-fault summary rides the manifest — a chaos-forced
        # storm must read as injected, not organic.
        injected = None
        with contextlib.suppress(Exception):
            from ditl_tpu.chaos import injected_summary

            summary = injected_summary()
            if summary is not None and summary.get("injected"):
                injected = summary
        manifest = {
            "schema": INCIDENT_SCHEMA,
            "flight_schema": FLIGHT_SCHEMA,
            "name": name,
            "trigger": anomaly.kind,
            "severity": anomaly.severity,
            "fingerprint": fp,
            "ts": anomaly.ts,
            "iso": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                 time.gmtime(anomaly.ts)),
            "detail": anomaly.detail,
            "source": self.source,
            "pid": os.getpid(),
            "suppressed_prior": suppressed_prior,
            "git_rev": _git_rev(),
            "files": None,  # filled below, after every file is written
        }
        if self.config_snapshot is not None:
            manifest["config"] = self.config_snapshot
        if injected is not None:
            manifest["injected_fault"] = injected
        manifest["files"] = sorted(files)
        with open(os.path.join(tmp, MANIFEST_NAME), "w") as f:
            json.dump(manifest, f, indent=2, sort_keys=True, default=str)
        # Chaos seam: a `kill` here dies BETWEEN writing the tmp dir and
        # the publishing rename — the torn-bundle drill (the tmp dir must
        # be invisible to --list and swept by the next manager).
        with contextlib.suppress(Exception):
            from ditl_tpu.chaos import maybe_inject

            maybe_inject("incident.dump")
        final = os.path.join(self.directory, name)
        os.rename(tmp, final)
        return final

    # -- retention ---------------------------------------------------------

    def _gc(self) -> None:
        """Oldest-first GC to the count and size caps (bundle names sort
        chronologically by construction). Never deletes the newest bundle
        — a single over-cap bundle is better evidence than none."""
        try:
            names = sorted(
                n for n in os.listdir(self.directory)
                if n.startswith(_BUNDLE_PREFIX)
            )
            while len(names) > self.max_bundles:
                shutil.rmtree(os.path.join(self.directory, names.pop(0)),
                              ignore_errors=True)
            if self.max_total_bytes > 0:
                sizes = [(n, _dir_bytes(os.path.join(self.directory, n)))
                         for n in names]
                total = sum(s for _, s in sizes)
                while total > self.max_total_bytes and len(sizes) > 1:
                    name, size = sizes.pop(0)
                    shutil.rmtree(os.path.join(self.directory, name),
                                  ignore_errors=True)
                    total -= size
        except OSError:
            logger.exception("incident: GC failed (bundles may exceed caps)")


# ---------------------------------------------------------------------------
# Reading side (CLI + /incidents endpoints)
# ---------------------------------------------------------------------------


def read_bundle(path: str) -> dict | None:
    """One bundle's manifest; None when torn/unreadable (a reader must
    skip, never crash — the journal's corrupt-tail rule)."""
    try:
        with open(os.path.join(path, MANIFEST_NAME)) as f:
            manifest = json.load(f)
    except (OSError, ValueError):
        return None
    if not isinstance(manifest, dict) or "trigger" not in manifest:
        return None
    manifest["path"] = path
    return manifest


def list_bundles(directory: str) -> list[dict]:
    """Every readable bundle manifest in ``directory``, oldest first.
    Hidden tmp dirs (mid-assembly or torn by a kill) and unreadable
    bundles are skipped silently."""
    out: list[dict] = []
    try:
        names = sorted(os.listdir(directory))
    except OSError:
        return out
    for name in names:
        if not name.startswith(_BUNDLE_PREFIX):
            continue
        manifest = read_bundle(os.path.join(directory, name))
        if manifest is not None:
            out.append(manifest)
    return out


def main(argv: list[str] | None = None) -> int:
    import argparse

    parser = argparse.ArgumentParser(
        prog="python -m ditl_tpu.telemetry.incident",
        description="list / inspect incident bundles (ISSUE 10)",
    )
    parser.add_argument("--dir", required=True,
                        help="incident directory (bundle dirs inside)")
    parser.add_argument("--list", action="store_true",
                        help="one line per bundle (the default)")
    parser.add_argument("--show", default="",
                        help="print one bundle's manifest JSON by name")
    args = parser.parse_args(argv)

    if args.show:
        manifest = read_bundle(os.path.join(args.dir, args.show))
        if manifest is None:
            print(f"no readable bundle {args.show!r} in {args.dir}")
            return 1
        print(json.dumps(manifest, indent=2, sort_keys=True))
        return 0
    bundles = list_bundles(args.dir)
    if not bundles:
        print(f"no incident bundles in {args.dir}")
        return 0
    for m in bundles:
        injected = " [injected_fault]" if m.get("injected_fault") else ""
        print(f"{m['name']}  {m['iso']}  {m['trigger']} "
              f"({m.get('severity', '?')}){injected}  "
              f"{len(m.get('files') or [])} file(s)")
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
