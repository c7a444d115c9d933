"""Device time by the program's own names: which scope or kernel of
``ditl_tpu/ops/names.py`` each executed instruction belongs to.

Where a name lands in a v5e trace (jax 0.9.0, libtpu 0.0.34; PERF.md section
5): a Mosaic kernel's ``name=`` becomes its instruction's own name
(``flash_fwd.16``), which ``reduce_trace`` and the ledger already print. A
``jax.named_scope`` path does not: it is the ``tf_op`` stat of the event's
*metadata* in the ``.xplane.pb`` (``jit(train_step)/transpose(jvp(loss))/
jit(fused_cross_entropy)/loss/while/body/.../dot_general:``), which
``jax.profiler.ProfileData`` does not show (its events carry only their own
stats: offset and duration). So this file reads the protobuf's wire format
itself: the few fields of ``XSpace`` it needs, with nothing but the standard
library. The run record's reduced trace cannot serve: it keeps 400 characters
of text an operation and no metadata.

An event is assigned to the innermost table name in its ``tf_op`` (a
kernel's path ends ``.../attn_core/flash_fwd/pallas_call:``): the last path
segment that equals a name once transformation wrappers (``jvp(...)``,
``transpose(...)``) are peeled; ``jit(<function>)`` segments are names of
functions and never count. Self time is ``reduce_trace.self_times``: a
``while`` gives its body's time to the body's instructions. Where XLA fuses
across a scope boundary the fusion carries one instruction's metadata (its
root's) and is charged whole to that scope; what carries no table name at
all is what ``scoped_time_share_train`` leaves over.

``SCOPES`` and ``KERNELS`` are this file's own copy of the program's table:
the yardstick. ``benchmarks/tests/test_scopes.py`` holds them equal.
"""

from __future__ import annotations

import functools
import glob
import gzip
import json
import os
import re
import sys

if __name__ == "__main__":  # run by hand: benchmarks/ is not on the path yet
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import reduce_trace
from harness import OUT

SCOPES = ("embed", "attn_qkv", "attn_core", "attn_out", "mlp", "layer_scan",
          "lm_head", "loss", "optimizer", "kv_gather", "kv_write", "sample")
KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "paged_attention",
           "mlp_bwd_act", "mlp_bwd_wgu", "proj_bwd")
TABLE = frozenset(SCOPES + KERNELS)

_JIT_SEGMENT = re.compile(r"jit\([^()]*\)")
_SEPARATORS = re.compile(r"[/()]")


def innermost(tf_op: str) -> str | None:
    """The table name an operation's scope path ends in, or None."""
    for segment in reversed(_SEPARATORS.split(_JIT_SEGMENT.sub("", tf_op))):
        if segment in TABLE:
            return segment
    return None


# --------------------------------------------------------------------------
# The protobuf wire format, as far as XSpace needs it (tsl/profiler/protobuf/
# xplane.proto; field numbers are the format's contract)
# --------------------------------------------------------------------------


def _varint(buf, i):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"wire type {wire} in an XSpace")
        yield key >> 3, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_entry(buf):
    key, value = 0, b""
    for no, v in _fields(buf):
        if no == 1:
            key = v
        elif no == 2:
            value = v
    return key, value


MODULES_LINE = "XLA Modules"
_PROGRAM_RUN = re.compile(r"\(\d+\)$")  # jit_paged_decode(1234...): the run's id


def _plane(buf) -> dict | None:
    """A device plane's ``XLA Ops`` events and the metadata they point at,
    with its ``XLA Modules`` events (one per program run), or None for any
    other plane."""
    name, lines, event_md, stat_md = "", [], [], {}
    for no, v in _fields(buf):
        if no == 2:
            name = _text(v)
        elif no == 3:
            lines.append(v)
        elif no == 4:
            event_md.append(v)
        elif no == 5:
            key, value = _map_entry(v)
            stat_md[key] = next((_text(x) for n, x in _fields(value) if n == 2), "")
    m = reduce_trace.DEVICE_PLANE.match(name)
    if not m:
        return None
    events, modules = [], []
    for line in lines:
        line_name, t0_ns, raw = "", 0, []
        for no, v in _fields(line):
            if no == 2:
                line_name = _text(v)
            elif no == 3:
                t0_ns = v
            elif no == 4:
                raw.append(v)
        if line_name not in (reduce_trace.OPS_LINE, MODULES_LINE):
            continue
        into = events if line_name == reduce_trace.OPS_LINE else modules
        for ev in raw:
            mid = offset_ps = dur_ps = 0
            for no, v in _fields(ev):
                if no == 1:
                    mid = v
                elif no == 2:
                    offset_ps = v
                elif no == 3:
                    dur_ps = v
            into.append([mid, t0_ns * 1000 + offset_ps, dur_ps])
    meta = {}
    for entry in event_md:
        key, value = _map_entry(entry)
        text, tf_op = "", ""
        for no, v in _fields(value):
            if no == 2:
                text = _text(v)
            elif no == 5:  # an XStat: its name by metadata_id, a string or a ref to one
                sid, s, ref = 0, None, None
                for n, x in _fields(v):
                    if n == 1:
                        sid = x
                    elif n == 5:
                        s = _text(x)
                    elif n == 7:
                        ref = x
                if stat_md.get(sid) == "tf_op":
                    tf_op = s if s is not None else stat_md.get(ref, "")
        meta[str(key)] = [reduce_trace.short_name(text), tf_op]
    modules = [[_PROGRAM_RUN.sub("", meta.get(str(mid), ["", ""])[0]), t, d]
               for mid, t, d in modules]
    return {"device": m.group(1), "events": events, "meta": meta, "modules": modules}


def load(path: str) -> dict:
    """{"devices": {n: [[metadata id, start ps, duration ps], ...]}, "meta":
    {n: {metadata id: [instruction name, tf_op]}}, "modules": {n: [[program,
    start ps, duration ps], ...]}} from an ``.xplane.pb``, or from the same as
    gzipped JSON (what ``tests/data`` keeps; a cut from before the serving
    cell has no ``modules``)."""
    if path.endswith(".json.gz"):
        with gzip.open(path, "rt") as f:
            return json.load(f)
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {"devices": {}, "meta": {}, "modules": {}}
    for no, v in _fields(space):
        if no == 1:
            plane = _plane(v)
            if plane and plane["events"]:
                out["devices"][plane["device"]] = plane["events"]
                out["meta"][plane["device"]] = plane["meta"]
                out["modules"][plane["device"]] = plane["modules"]
    return out


# --------------------------------------------------------------------------
# From events to seconds by name
# --------------------------------------------------------------------------


def seconds_by_name(trace: dict, program: str | None = None) -> dict:
    """{table name: seconds of self time, mean over the chips}; the key None
    holds what ran under no table name. Empty without a device event.
    ``program``: only the operations of that jitted function (``paged_decode``:
    those whose path begins ``jit(paged_decode)/``)."""
    n = len(trace["devices"])
    prefix = f"jit({program})/" if program else ""
    out: dict = {}
    for dev, events in trace["devices"].items():
        meta = trace["meta"][dev]
        for mid, self_ps, _leaf in reduce_trace.self_times(events):
            tf_op = meta.get(str(mid), ["", ""])[1]
            if not tf_op.startswith(prefix):
                continue
            name = innermost(tf_op)
            out[name] = out.get(name, 0.0) + self_ps / 1e12 / n
    return out


def seconds_by_program(trace: dict) -> dict:
    """{program (``jit_paged_decode``): seconds its runs held the device,
    mean over the chips}, from the ``XLA Modules`` line."""
    modules = trace.get("modules", {})
    out: dict = {}
    for runs in modules.values():
        for name, _start, dur_ps in runs:
            out[name] = out.get(name, 0.0) + dur_ps / 1e12 / len(modules)
    return out


def trace_file(run: dict) -> str | None:
    """The newest trace under the workload's run directories: the one the
    run that is being read has just written."""
    found = glob.glob(os.path.join(OUT, "runs", f"{run['workload']}.s*.t1*", "trace",
                                   "plugins", "profile", "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


@functools.lru_cache(maxsize=1)  # every reader asks for the same run's trace
def _loaded(path: str) -> dict:
    return load(path)


@functools.lru_cache(maxsize=4)
def _seconds_of(path: str, program: str | None = None) -> dict:
    return seconds_by_name(_loaded(path), program)


def run_seconds_by_name(run: dict, program: str | None = None) -> dict | None:
    """``seconds_by_name`` of the run's own trace; None where there is no
    trace, or none of its events carries a table name (a program from
    before the names, a CPU rehearsal)."""
    path = trace_file(run) if run.get("trace") is not None else None
    if path is None:
        return None
    by_name = _seconds_of(path, program)
    return by_name if any(k is not None for k in by_name) else None


def time_share(run: dict, names, program: str | None = None) -> float | None:
    """Self time under ``names`` (inside ``program`` only, where given) over
    the trace's busy time, in percent, mean over the chips. None, never 0,
    where the trace has none of them."""
    by_name = run_seconds_by_name(run, program)
    if by_name is None or not any(n in by_name for n in names):
        return None
    return 100.0 * sum(by_name.get(n, 0.0) for n in names) / run["trace"]["busy_s"]


def program_share(run: dict, program: str) -> float | None:
    """Device time of ``program``'s runs (``jit_paged_prefill``) over the
    trace's busy time, in percent: 0.0 where the trace holds no such run,
    None only where the run has no trace (a line that lacks a listed metric
    is a refusal: ledger, PR 30)."""
    path = trace_file(run) if run.get("trace") is not None else None
    if path is None:
        return None
    return 100.0 * seconds_by_program(_loaded(path)).get(program, 0.0) / run["trace"]["busy_s"]


def gap_share(run: dict, label: str) -> float | None:
    """Idle time of device 0 whose middle lies in a host span named ``label``
    (``reduce_trace``'s ``gap_s_by_label``) over the traced window, in
    percent: 0.0 where no gap carries the label, None only without a trace."""
    if run.get("trace") is None:
        return None
    return 100.0 * run["trace"].get("gap_s_by_label", {}).get(label, 0.0) / run["trace"]["window_s"]


def dump(trace: dict, path: str, from_s: float, to_s: float) -> None:
    """Write the events that lie inside [from_s, to_s) of the trace's clock
    (a ``while`` that reaches beyond the cut would keep time its body's
    instructions were given), with the metadata they use, as the gzipped
    JSON that ``load`` reads."""
    lo, hi = from_s * 1e12, to_s * 1e12
    cut = {"devices": {}, "meta": {}, "modules": {}}
    inside = lambda e: lo <= e[1] and e[1] + e[2] <= hi  # noqa: E731
    for dev, events in trace["devices"].items():
        kept = [e for e in events if inside(e)]
        used = {str(e[0]) for e in kept}
        cut["devices"][dev] = kept
        cut["meta"][dev] = {k: v for k, v in trace["meta"][dev].items() if k in used}
        cut["modules"][dev] = [e for e in trace.get("modules", {}).get(dev, []) if inside(e)]
    with gzip.open(path, "wt") as f:
        json.dump(cut, f, separators=(",", ":"))


def main(argv: list[str]) -> int:
    """python benchmarks/layer_metrics/_scopes.py FILE [--dump OUT --from-s A
    --to-s B]: seconds and share of the traced self time by name."""
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("file")
    ap.add_argument("--dump")
    ap.add_argument("--from-s", type=float, default=0.0)
    ap.add_argument("--to-s", type=float, default=1e9)
    args = ap.parse_args(argv)
    trace = load(args.file)
    if args.dump:
        dump(trace, args.dump, args.from_s, args.to_s)
        return 0
    by_name = seconds_by_name(trace)
    total = sum(by_name.values())
    for name, s in sorted(by_name.items(), key=lambda kv: -kv[1]):
        print(f"{name or '(no table name)':18s} {s:10.6f} s {100 * s / total:6.2f}%")
    for name, s in sorted(seconds_by_program(trace).items(), key=lambda kv: -kv[1]):
        print(f"program {name:28s} {s:10.6f} s {100 * s / total:6.2f}%")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
