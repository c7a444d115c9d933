"""The decode program's grouped expert matmuls against the memory roofline:
the least time HBM needs to deliver the touched experts' weights
(``moe_bytes.decode_expert_bytes``: decode steps x layers x experts touched a
step and layer x one expert's three matrices, over the chip's peak bytes/s)
over the device self time under ``moe_experts`` in the recorded runs of
``jit_paged_decode``. Memory-bound: at ~35 rows x 8 choices a step the
matmuls' operations need a thirtieth of the time their bytes do. Steps are
the recorded runs times the steps of a tick; the touched mean is the
server's own count over the measured window (``_moe.tick_rows``)."""
import moe_bytes
from layer_metrics import _moe

LAYER = "Kernels"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def read(run):
    by = _moe.run_seconds(run, program="paged_decode", inside_runs_of="jit_paged_decode")
    rows = _moe.tick_rows(run)
    if by is None or not rows or not by.get("moe_experts") or not by.get("runs"):
        return None
    layers = run["config"]["num_hidden_layers"]
    steps = by["runs"] * rows[0]["moe_steps"]
    least_s = moe_bytes.decode_expert_bytes(
        run["config"], steps, _moe.touched_mean(rows, layers)
    ) / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / by["moe_experts"]
