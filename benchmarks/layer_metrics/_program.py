"""What the readers of the program's own record share.

The program keeps its cold spans (start-up, every compilation phase) in
the process: ``horovod_tpu.spans()``, each with its wall-clock start
and end.  A reader runs in rank 0's process before ``hvd.shutdown()``
and gets only ``run``, so it takes the record from the process itself
and places the measured window on the record's clock from two numbers
of ``run``: ``hvd.init()`` returned ``init_s`` after the command's
start (the end of the newest ``hvd/init`` span is that instant) and the
window opened ``setup_s`` after it.  Only spans of this run, ended
before the window, count: the traced run compiles its step once more
after the window, for the HLO text.

A program without the record (any commit before the spans) gives
nothing, and neither does a ``run`` no trainer made.
"""

import statistics

INIT = "hvd/init"
COMPILE = "hvd/compile/"
EXCHANGE = "hvd/exchange"


def cold_spans(run: dict):
    """The process's cold spans, or None where ``run`` is no trainer's
    or the program keeps none."""
    if run.get("setup_s") is None or run.get("init_s") is None:
        return None
    import horovod_tpu
    spans = getattr(horovod_tpu, "spans", None)
    return spans() if spans is not None else None


def init_span(run: dict):
    """The ``hvd/init`` span of this run (the newest), or None."""
    inits = [s for s in cold_spans(run) or [] if s["name"] == INIT]
    return inits[-1] if inits else None


def before_window(run: dict, phases):
    """The ``hvd/compile/<phase>`` spans of ``phases`` that began after
    the command's start and ended before the window opened; None where
    there is no record."""
    init = init_span(run)
    if init is None:
        return None
    start = init["end"] - run["init_s"]
    window = init["end"] + run["setup_s"] - run["init_s"]
    names = {COMPILE + p for p in phases}
    return [s for s in cold_spans(run) if s["name"] in names
            and s["start"] >= start and s["end"] <= window]


def longest(spans, n: int = 5):
    """The ``n`` longest of ``spans`` as ``[phase, program, seconds]``,
    for an information line."""
    top = sorted(spans, key=lambda s: s["start"] - s["end"])[:n]
    return [[s["name"][len(COMPILE):], s["args"].get("program"),
             round(s["end"] - s["start"], 2)] for s in top]


def uncovered(run: dict, n: int = 4):
    """The ``n`` longest stretches between ``hvd.init()`` returning and
    the window in which no cold span was open, as ``[the span that ended
    before it, its program, seconds]``: where set-up time goes that no
    span sees (a device client starting, imports, the first execution of
    a loaded program)."""
    init = init_span(run)
    window = init["end"] + run["setup_s"] - run["init_s"]
    reach, after, gaps = init["end"], init, []
    for s in sorted(cold_spans(run), key=lambda s: s["start"]):
        if s["start"] < init["end"] or s["end"] > window:
            continue
        if s["start"] > reach:
            gaps.append((s["start"] - reach, after))
        if s["end"] > reach:
            reach, after = s["end"], s
    gaps.append((window - reach, after))
    gaps.sort(key=lambda g: -g[0])
    return [[g[1]["name"], g[1]["args"].get("program"), round(g[0], 2)]
            for g in gaps[:n]]


def covered_s(spans) -> float:
    """Seconds covered by the spans' intervals, counted once where they
    overlap: a function traced inside another's trace is inside it."""
    total, reach = 0.0, float("-inf")
    for start, end in sorted((s["start"], s["end"]) for s in spans):
        if end > reach:
            total += end - max(start, reach)
            reach = end
    return total


def per_step_ms(spans, name: str):
    """Median, over the steps, of the milliseconds inside spans called
    ``name`` from the start of one ``hvd/exchange`` span to the start of
    the next; None with fewer than two exchanges.  ``spans`` have
    ``name``, ``start`` and ``end`` in seconds on one clock."""
    marks = sorted(s["start"] for s in spans if s["name"] == EXCHANGE)
    if len(marks) < 2:
        return None
    steps = [0.0] * (len(marks) - 1)
    for s in spans:
        if s["name"] != name:
            continue
        for i in range(len(steps)):
            overlap = min(s["end"], marks[i + 1]) - max(s["start"], marks[i])
            if overlap > 0:
                steps[i] += overlap
    return 1e3 * statistics.median(steps)


def traced_spans(run: dict):
    """The program's spans of the traced steps, where the trace's
    reduction kept them (``program_spans``); None where it did not."""
    return (run.get("trace") or {}).get("program_spans")
