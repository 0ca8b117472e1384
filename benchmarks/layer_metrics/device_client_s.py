"""Seconds the device client took to start on rank 0: the
``hvd/init/device_client`` span, the process's first ``jax.devices()``,
which ``hvd.init()`` makes for every world size since PR 34.  It is a
part of ``hvd_init_s`` and of ``init_s``."""

from benchmarks.layer_metrics import _program, _setup

LAYER = "Launch and start-up"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "setup_s"


def read(run: dict):
    init = _program.init_span(run)
    if init is None:
        return None
    mine = [s for s in _setup.named(run, _setup.DEVICE_CLIENT,
                                    init["start"], init["end"])
            if s["parent"] == init["name"]]
    if not mine:
        return None
    client = mine[-1]
    print("bench: device client %.3f s of hvd/init %.3f: %s"
          % (client["end"] - client["start"], init["end"] - init["start"],
             client["args"]), flush=True)
    return client["end"] - client["start"]
