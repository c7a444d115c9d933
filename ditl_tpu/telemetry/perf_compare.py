"""Row/sweep regression gate (ISSUE 7 tentpole leg 3).

    python -m ditl_tpu.telemetry.perf_compare old.json new.json \
        [--threshold 0.05]

Diffs two performance records — either two single rows (one JSON object
each, saved to a file) or two versioned sweep records
(``telemetry.perf.run_recorded_cells``) — metric by metric against a
relative threshold, and **exits nonzero on regression**. Since PR 28 its
callers are tests (the benchmark, ``benchmarks/``, compares its own runs);
the rows it names below are the fleet drills' (``tests/gateway_drivers.py``).

Comparison rules:

- Each known metric has a direction: throughput/MFU regress when they FALL,
  step time regresses when it RISES. Unknown keys are ignored (records may
  grow fields without breaking old gates).
- Sweep records compare cell-by-cell on the cell key (the dotted-override
  spec), so only identical configurations are ever diffed; cells present
  only on one side are reported but do not gate (a grown grid is not a
  regression).
- Mismatched schema versions or record shapes are a usage error (exit 2),
  never a silent pass.

Exit codes: 0 = within thresholds, 1 = regression, 2 = usage/parse error.
"""

from __future__ import annotations

import argparse
import json
import sys

from ditl_tpu.telemetry.perf import SWEEP_SCHEMA

__all__ = ["compare_metrics", "compare_records", "main"]

# Metric -> direction: +1 = higher is better (regression when it falls),
# -1 = lower is better (regression when it rises).
COMPARE_KEYS = {
    "value": +1,  # bench headline (tokens/sec[/chip])
    "tokens_per_sec_per_chip": +1,
    "mfu": +1,
    "mfu_cost": +1,
    "roofline_mfu_cap": 0,  # informational: config property, never gates
    "step_time_p50_ms": -1,
    "step_ms": -1,
    # Serving-row keys (ISSUE 8, fleet rows' hoisted `serving`
    # block): scheduler-interference p95 regresses when it RISES (a stall
    # crept back into the budgeted tick composition); the measured
    # prefix-cache hit ratio regresses when it FALLS (routing or paging
    # stopped reusing KV). p50 and totals are reported-not-gated noise.
    "interference_p95_s": -1,
    "prefix_cache_hit_ratio": +1,
    "ttft_p95_s": -1,
    # Disaggregated-fleet A/B keys (ISSUE 9): the heterogeneous-fleet rows
    # are graded on INTERACTIVE latency specifically — batch work is
    # supposed to absorb the prefill burden, so only the interactive split
    # gates (batch p95s are reported context, not regressions).
    "interactive_interference_p95_s": -1,
    "interactive_ttft_p95_s": -1,
    # Autoscaler A/B keys (ISSUE 12, trace-replay rows'
    # hoisted `autoscale` block): replica_seconds is the resource cost the
    # autoscaler exists to cut (regresses when it RISES — the on-vs-off
    # A/B gates it next to the ttft_p95_s already above); the interactive
    # TTFT-SLO violation rate regresses when it rises (scaling must not
    # buy replica-seconds with burned SLO budget).
    "replica_seconds": -1,
    "ttft_slo_violation_rate": -1,
    # KV movement plane keys (ISSUE 13, the tier/handoff bench blocks —
    # `schema`-stamped like the PR 8 serving block): the host-tier hit
    # ratio regresses when it FALLS (the tier stopped absorbing eviction
    # churn — 0.0 on tier-off rows never gates, the a == 0 rule); swap-in
    # p95 regresses when it RISES (host hits are only wins while the
    # device_put stays cheap); the handoff fallback ratio regresses when
    # it rises (shipped prefills failing back to re-prefill means the
    # handoff plane is burning work, not saving it).
    "host_tier_hit_ratio": +1,
    "swap_in_p95_s": -1,
    "handoff_fallback_ratio": -1,
    # Gateway data-plane overhead keys (ISSUE 14, gateway-overhead
    # rows' hoisted `gateway_overhead` block):
    # the stub-replica closed loop isolates the gateway's OWN per-request
    # tax from any device work, so these gate host-side regressions the
    # device benches can't see. Requests/sec through the gateway regresses
    # when it falls; the added latency vs hitting a replica directly
    # regresses when it rises (p50 = the steady tax, p95 = the tail the
    # connect-per-request churn used to own). The pool hit ratio is
    # reported context, not gated — it is 0.0 by construction on the
    # fresh-connect A/B leg.
    "gateway_rps": +1,
    "gateway_added_p50_s": -1,
    "gateway_added_p95_s": -1,
    # Event-loop data plane keys (ISSUE 17, same hoisted block): the
    # evloop-vs-threaded throughput ratio at the legacy concurrency
    # point regresses when it falls below parity — the new plane may
    # never hide a per-request slowdown behind its concurrency win; the
    # max resident gateway thread count during the --serve-concurrency
    # stream hold regresses when it RISES — the whole point of the
    # selector loop is that N open streams cost ~13 threads, not ~N.
    "evloop_vs_threaded_rps_ratio": +1,
    "gateway_max_resident_threads": -1,
    # Usage-metering keys (ISSUE 15, metered gateway-overhead
    # rows' hoisted `usage_metering` block): the
    # metered leg's requests/sec regresses when it falls, and the
    # fractional rps cost of arming the ledger regresses when it rises —
    # per-tenant accounting must stay cheap enough that nobody is
    # tempted to turn billing off under load.
    "gateway_rps_metered": +1,
    "metering_overhead_ratio": -1,
    # Adapter plane keys (ISSUE 16, multi-LoRA serving rows' hoisted
    # `adapters` block): the fractional throughput cost of serving through
    # the stacked adapter gather (vs the base-only A/B leg) regresses when
    # it rises — multi-tenant LoRA is only viable while the per-request
    # gather tax stays a few percent; and the p95 hot-swap wall (verify ->
    # install -> flip) regresses when it rises — a slow swap stretches the
    # window where a publication holds a spare row.
    "adapter_gather_overhead_ratio": -1,
    "adapter_swap_p95_s": -1,
    # Continuous-profiling keys (ISSUE 18, gateway-overhead
    # rows' hoisted `profiler_overhead` block): the profiler-on vs
    # profiler-off req/s ratio regresses when it falls — the always-on
    # sampler + loop-lag watchdog are only "always-on" while they cost
    # within the same-box noise floor of running dark.
    "prof_vs_off_rps_ratio": +1,
    # Bulk-lane goodput keys (ISSUE 19, bulk-backlog replay rows'
    # hoisted `bulk` block): the lane's tokens/sec regresses when it
    # falls — spare decode capacity the offline backlog stopped soaking
    # is throughput thrown away; and the interactive TTFT p95 measured
    # WITH the backlog running regresses when it rises — the lane's
    # whole contract is zero interactive SLO burn, so bulk-induced
    # interference is a regression of the lane, not of the fleet.
    "bulk_tokens_per_s": +1,
    "bulk_interactive_ttft_p95_s": -1,
}

# Per-key noise floors: gated keys whose honest run-to-run spread on a
# shared box exceeds the default threshold. The evloop-vs-threaded
# ratio is a quotient of two same-box closed loops — a paired-median
# estimator cancels drift, but ~±10% spread at parity
# survives it, so gating the ratio at the generic 5% flags the box's
# mood as a data-plane regression. 15% still catches any real
# per-request slowdown while two honest parity rows compare clean.
# The effective threshold is max(--threshold, floor): a caller asking
# for a LOOSER gate than the floor gets what they asked for.
KEY_THRESHOLDS = {
    "evloop_vs_threaded_rps_ratio": 0.15,
    # Same estimator shape, same box: a quotient of two closed loops.
    "prof_vs_off_rps_ratio": 0.15,
}


def _flat(rec: dict) -> dict:
    """The comparable view of one record/cell: top-level keys plus the
    nested ``roofline`` (train rows), ``serving`` (serve rows),
    ``autoscale`` (trace-replay rows), ``kv_handoff`` (handoff-armed
    gateway rows, ISSUE 13), and ``gateway_overhead`` (stub-fleet
    overhead rows, ISSUE 14), ``usage_metering`` (metering-armed
    overhead rows, ISSUE 15), and ``adapters`` (multi-LoRA serving rows,
    ISSUE 16) blocks hoisted — without the hoist the gate
    would silently never compare cost-counted MFU, the serving scheduler
    metrics, the replica-seconds the autoscaler A/B is graded on, the
    handoff fallback ratio, or the gateway's own per-request tax."""
    out = rec
    for block in ("roofline", "serving", "autoscale", "kv_handoff",
                  "gateway_overhead", "usage_metering", "adapters",
                  "profiler_overhead", "bulk"):
        nested = rec.get(block)
        if isinstance(nested, dict):
            out = {**nested, **out}
    return out


def compare_metrics(
    old: dict, new: dict, threshold: float, label: str
) -> tuple[list[str], list[str]]:
    """(report lines, regression lines) for one old/new metric-dict pair.
    A record that went from measured to errored is itself a regression —
    a config that now crashes must not pass the gate because it has no
    numbers to compare."""
    lines: list[str] = []
    regressions: list[str] = []
    old, new = _flat(old), _flat(new)
    # Incident gating (ISSUE 10 satellite): bench rows embed the run's
    # assembled-incident count. NEW incidents on the new side are a
    # "now fails"-class regression — a perf lever that wins throughput by
    # provoking anomaly storms (deadline expiries, preemption thrash) must
    # not pass the gate on its throughput numbers. When BOTH sides had
    # incidents the comparison is reported, not gated (a known-noisy
    # config's storms are context, not a new regression).
    old_inc, new_inc = old.get("incidents"), new.get("incidents")
    if isinstance(new_inc, (int, float)) and new_inc > 0:
        if not old_inc:
            msg = (f"{label}incidents: 0 -> {int(new_inc)} (anomaly "
                   "bundles on the new side; previously clean)")
            lines.append(f"  {msg} REGRESSION")
            regressions.append(msg)
        else:
            lines.append(
                f"  {label}incidents: {int(old_inc)} -> {int(new_inc)} "
                "(both sides had incidents; reported, not gated)"
            )
    # Handoff-fallback gating (ISSUE 13): the generic direction loop
    # below skips keys whose old value is 0 (no relative delta exists),
    # which would make the fallback-ratio gate vacuous in exactly the
    # normal case — a previously CLEAN handoff plane (ratio 0.0). Treat
    # 0 -> >0 like incidents: fallbacks appearing is a regression class
    # of its own, not a percentage move.
    old_fb = old.get("handoff_fallback_ratio")
    new_fb = new.get("handoff_fallback_ratio")
    if (isinstance(new_fb, (int, float)) and new_fb > 0
            and isinstance(old_fb, (int, float)) and old_fb == 0):
        msg = (f"{label}handoff_fallback_ratio: 0 -> {new_fb:g} (shipped "
               "prefills now failing back to re-prefill; previously clean)")
        lines.append(f"  {msg} REGRESSION")
        regressions.append(msg)
    # Invariant-lint gating (ISSUE 11 satellite): rows stamp
    # `analysis_clean` (bench runs `ditl_tpu.analysis` once per process).
    # clean -> dirty is a "now fails"-class regression — a perf win that
    # ships an invariant violation (a stray sync, an unguarded attribute)
    # must not pass on its numbers. Both-sides-dirty is reported, not
    # gated; rows predating the stamp (absent) are skipped.
    old_an, new_an = old.get("analysis_clean"), new.get("analysis_clean")
    if new_an is False:
        if old_an is True:
            msg = (f"{label}analysis_clean: true -> false (invariant "
                   "lint now fails; run python -m ditl_tpu.analysis)")
            lines.append(f"  {msg} REGRESSION")
            regressions.append(msg)
        else:
            lines.append(
                f"  {label}analysis_clean: false on "
                f"{'both sides' if old_an is False else 'new side only'} "
                "(reported, not gated)"
            )
    if new.get("error") and not old.get("error"):
        msg = (f"{label}previously measured, now fails: "
               f"{str(new['error'])[:200]}")
        lines.append(f"  {msg} REGRESSION")
        regressions.append(msg)
        return lines, regressions
    if old.get("error"):
        state = "still failing" if new.get("error") else "now measured"
        lines.append(f"  {label}old record errored ({state}; not gated)")
        return lines, regressions
    for key, direction in COMPARE_KEYS.items():
        a, b = old.get(key), new.get(key)
        if not isinstance(a, (int, float)) or not isinstance(b, (int, float)):
            continue
        if a == 0:
            continue
        rel = (b - a) / abs(a)
        # Signed "improvement" in the metric's own direction.
        gain = rel * direction
        key_threshold = max(threshold, KEY_THRESHOLDS.get(key, 0.0))
        verdict = "ok"
        if direction != 0 and gain < -key_threshold:
            verdict = "REGRESSION"
            regressions.append(
                f"{label}{key}: {a:g} -> {b:g} ({rel:+.1%}, threshold "
                f"{key_threshold:.0%})"
            )
        lines.append(f"  {label}{key}: {a:g} -> {b:g} ({rel:+.1%}) {verdict}")
    return lines, regressions


def _is_sweep(rec: dict) -> bool:
    return isinstance(rec.get("cells"), dict)


def compare_records(old: dict, new: dict, threshold: float) -> tuple[int, str]:
    """(exit code, human report). Accepts two bench rows or two sweep
    records; mixing shapes is a usage error."""
    out: list[str] = []
    regressions: list[str] = []
    if _is_sweep(old) != _is_sweep(new):
        return 2, "error: cannot compare a sweep record with a bench row"
    for side, rec in (("old", old), ("new", new)):
        schema = rec.get("schema")
        if schema is not None and schema != SWEEP_SCHEMA:
            return 2, (
                f"error: {side} record has schema {schema!r}; this tool "
                f"understands schema {SWEEP_SCHEMA}"
            )
    if _is_sweep(old):
        old_cells, new_cells = old["cells"], new["cells"]
        common = [k for k in old_cells if k in new_cells]
        if not common:
            return 2, "error: the two sweep records share no cells"
        for side, only in (
            ("old", sorted(set(old_cells) - set(new_cells))),
            ("new", sorted(set(new_cells) - set(old_cells))),
        ):
            for k in only:
                out.append(f"  [{k}] only in {side} record (not gated)")
        for k in sorted(common):
            lines, regs = compare_metrics(
                old_cells[k], new_cells[k], threshold, f"[{k}] "
            )
            out.extend(lines)
            regressions.extend(regs)
    else:
        m_old, m_new = old.get("metric"), new.get("metric")
        if m_old != m_new:
            out.append(
                f"  warning: metric labels differ ({m_old!r} vs {m_new!r}) "
                "— comparing anyway; make sure the configs match"
            )
        lines, regs = compare_metrics(old, new, threshold, "")
        out.extend(lines)
        regressions.extend(regs)
        if not lines:
            return 2, "error: no comparable numeric metrics in the records"
    if regressions:
        out.append("")
        out.append(f"FAIL: {len(regressions)} regression(s)")
        out.extend(f"  {r}" for r in regressions)
        return 1, "\n".join(out)
    out.append("")
    out.append(f"PASS: no metric regressed past {threshold:.0%}")
    return 0, "\n".join(out)


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m ditl_tpu.telemetry.perf_compare",
        description="diff two bench/sweep JSON records; exit 1 on regression",
    )
    parser.add_argument("old", help="baseline record (bench row or sweep JSON)")
    parser.add_argument("new", help="candidate record to gate")
    parser.add_argument(
        "--threshold", type=float, default=0.05,
        help="relative regression threshold (default 0.05 = 5%%)",
    )
    args = parser.parse_args(argv)
    if not 0 < args.threshold < 1:
        print(f"error: --threshold must be in (0, 1), got {args.threshold}",
              file=sys.stderr)
        return 2
    records = []
    for path in (args.old, args.new):
        try:
            with open(path) as f:
                rec = json.load(f)
        except (OSError, ValueError) as e:
            print(f"error: cannot read {path}: {e}", file=sys.stderr)
            return 2
        if not isinstance(rec, dict):
            print(f"error: {path} is not a JSON object", file=sys.stderr)
            return 2
        records.append(rec)
    code, report = compare_records(records[0], records[1], args.threshold)
    print(f"perf_compare: {args.old} -> {args.new}")
    print(report)
    return code


if __name__ == "__main__":
    sys.exit(main())
