"""Seconds the trainer had spent lowering and compiling (or loading from the
persistent cache) when the window opened: ``compile_s_cum`` of the window's
first ``metrics_file`` row (``compile_s_in_window_train`` says whether any
was added later). The reference check's own compiles are not in it: it runs
before the program starts. Left out where the program writes no such field."""
LAYER = "Runtime"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(run):
    rows = [r for r in run["rows"] if "compile_s_cum" in r]
    return rows[0]["compile_s_cum"] if rows else None
