"""Time inside all-gather / reduce-scatter / all-reduce events during which
no other operation ran on that device, over the traced window; mean over the
chips. The part of communication that compute does not hide."""
LAYER = "Runtime"
UNIT = "%"
MOVES = "train_tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    t = run["trace"]
    if t is None or t["collective_s"] == 0:
        return None
    return 100.0 * t["collective_exposed_s"] / t["window_s"]
