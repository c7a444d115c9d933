"""Process start to the first instant of the measured window: runtime up,
weights made on the device, compilation or cache load, warm-up, pre-roll,
and in a cell's first run in a checkout the reference check."""
UNIT = "s"


def read(run):
    return run["setup_s"]
