"""Host seconds around the first call of every program of the cell
(init, step or grad, and in the eager loop the first exchange and
update), less one steady call of the step: what compiling, or loading
from the persistent cache, costs this run."""

LAYER = "Compile"
UNIT = "s"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "setup_s"


def read(run: dict):
    return run.get("compile_s")
