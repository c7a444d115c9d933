"""Seconds of the reference check (``chip_child.py``, before the program
starts): ``reference.seconds`` of the run record. A checkout's first run of a
cell makes the check (tens to hundreds of seconds); later runs read the kept
verdict. It is a part of ``setup_before_program_s``. Needs nothing of the
program, so a parent reports it too."""
from layer_metrics import _setup

LAYER = "Runtime"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "host_clock"


def read(run):
    return _setup.reference_s(run)
