"""Of the rectangle of every slot by every page-table position and the tail,
the share of steps a call of the decode attention kernels walks: the sum of
``attn_steps_walked`` over the sum of ``attn_steps_rect`` of the server's
``engine.tick`` spans (``--trace-dir``) that began inside the window. Both
are counts of ONE call (every call of a tick walks the same list:
``ditl_tpu/ops/paged_attention.py`` ``decode_steps``), which the decode
program returns with the tick's tokens. 100.0 where no span carries the
counter: a program that does not count walks the rectangle, live or dead.
None only without a traced run."""
import json

from layer_metrics import _ttft

LAYER = "Kernels"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "program_counter"


def walked_share(paths, wall0: float, wall1: float) -> float:
    walked = rect = 0
    for p in paths:
        with open(p) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if (rec.get("event") == "trace.span" and rec.get("name") == "engine.tick"
                        and rec.get("attn_steps_rect") and wall0 <= rec["ts"] < wall1):
                    walked += rec["attn_steps_walked"]
                    rect += rec["attn_steps_rect"]
    return 100.0 * walked / rect if rect else 100.0


def read(run):
    run_dir = _ttft.run_dir_of(run)
    if run_dir is None:
        return None
    return walked_share(_ttft.journal_paths(run_dir), *run["window_wall"])
