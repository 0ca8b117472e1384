"""What the readers of set-up's spans share.

Since PR 34 the program keeps a cold span around the device client's
start (``hvd/init/device_client``), around each late import
(``hvd/import`` with ``module=``), around the abstract trace that lays
out a step's state (``hvd/step/shardings``) and around the first call
of each program a step builder hands out (``hvd/program/first_call``).
The readers place them between three instants of ``run`` on the
record's clock (``_program``'s rule): the command's start, ``hvd.init()``
returning and the window's opening.  A program that keeps none of these
spans (any commit before them) gives nothing.
"""

from benchmarks.layer_metrics import _program

DEVICE_CLIENT = "hvd/init/device_client"
IMPORT = "hvd/import"
SHARDINGS = "hvd/step/shardings"
FIRST_CALL = "hvd/program/first_call"


def instants(run: dict):
    """``(command's start, hvd.init() returned, window opened)`` in
    wall-clock seconds, or None where there is no record."""
    init = _program.init_span(run)
    if init is None:
        return None
    return (init["end"] - run["init_s"], init["end"],
            init["end"] + run["setup_s"] - run["init_s"])


def named(run: dict, name: str, since: float, until: float):
    """The cold spans called ``name`` that began at ``since`` or later
    and ended by ``until``."""
    return [s for s in _program.cold_spans(run) if s["name"] == name
            and s["start"] >= since and s["end"] <= until]


def compile_s_inside(run: dict, outer: dict) -> float:
    """Seconds of ``outer`` that its ``hvd/compile/*`` children cover."""
    return _program.covered_s(
        s for s in _program.cold_spans(run)
        if s["name"].startswith(_program.COMPILE)
        and s["parent"] == outer["name"]
        and s["start"] >= outer["start"] and s["end"] <= outer["end"])


def after_init(run: dict):
    """Every cold ``hvd/`` span that was open between ``hvd.init()``
    returning and the window, cut to that interval; None where the
    program spans no program's first call there."""
    at = instants(run)
    if at is None or not named(run, FIRST_CALL, at[1], at[2]):
        return None
    return [dict(s, start=max(s["start"], at[1]), end=min(s["end"], at[2]))
            for s in _program.cold_spans(run)
            if s["end"] > at[1] and s["start"] < at[2]]
