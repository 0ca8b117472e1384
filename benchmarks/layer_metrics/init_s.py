"""Seconds from the command's start to ``hvd.init()`` returning on
rank 0: the launcher, the interpreter, the imports, the rendezvous."""

LAYER = "Launch and start-up"
UNIT = "s"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "setup_s"


def read(run: dict):
    return run.get("init_s")
