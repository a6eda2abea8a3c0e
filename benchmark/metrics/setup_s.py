"""Process start to the first instant of the measured window, the plain
reference's own time left out: imports, weights made on the device, compile
or cache load, the program's first steps, warm-up."""
LAYER = "end to end"
MOVES = None
UNIT = "s"
SOURCE = "host_clock"


def read(facts):
    return facts["setup_s"]
