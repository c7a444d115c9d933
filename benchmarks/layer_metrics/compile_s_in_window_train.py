"""Seconds the trainer spent lowering and compiling (or loading from the
persistent cache) between the window's first and last ``metrics_file`` rows:
the difference of their ``compile_s_cum``. Must read 0. Left out where the
program writes no such field."""
LAYER = "Runtime"
UNIT = "s"
MOVES = "train_tokens_per_s_per_chip"
SOURCE = "program_counter"


def read(run):
    rows = [r for r in run["rows"] if "compile_s_cum" in r]
    if not rows:
        return None
    return rows[-1]["compile_s_cum"] - rows[0]["compile_s_cum"]
