"""Device self time of the selection's three parts over the traced window's
busy time: the lightning indexer (its projections and its scores, scope
``dsa_index``), the top-k and the page arithmetic (``dsa_select``) and the
read of the selected latent entries (``dsa_gather``); decode and prefill.
What sparse attention costs beyond the attention it leaves. Nothing to read
where the program has no indexer."""
from layer_metrics import _dsa

LAYER = "Kernels"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def read(run):
    return _dsa.time_share(run, _dsa.SELECTION)
