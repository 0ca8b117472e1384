"""The readers of the program's own record (``hvd.spans()``): each on a
made-up record, on a run no trainer made, and on a record that holds a
compilation after the window opened."""

import pytest

from benchmark_tiny import spec

T0 = 1_000_000.0          # the command's start on the made-up wall clock
RUN = {"init_s": 6.0, "setup_s": 40.0}
WINDOW = T0 + RUN["setup_s"]


def _span(name, start, seconds, parent=None, **args):
    return {"name": name, "start": start, "end": start + seconds,
            "thread": "MainThread", "parent": parent, "args": args}


def _record(after_window=()):
    """A run's cold spans: an older run of the same process first, then
    this run's start-up and its compilations before the window."""
    return [
        _span("hvd/import", T0 - 500.0, 2.0),
        _span("hvd/init", T0 - 490.0, 9.0),
        _span("hvd/compile/backend_compile", T0 - 480.0, 100.0, program="old"),
        _span("hvd/import", T0 + 1.0, 2.5),
        _span("hvd/init/rendezvous", T0 + 4.0, 0.25, "hvd/init"),
        _span("hvd/init/backend", T0 + 4.25, 1.5, "hvd/init"),
        _span("hvd/init/runtime", T0 + 5.75, 0.25, "hvd/init"),
        _span("hvd/init", T0 + 4.0, 2.0),
        # _init: traced (a nested trace inside), lowered, loaded.
        _span("hvd/compile/trace", T0 + 8.0, 1.0, program="dot"),
        _span("hvd/compile/trace", T0 + 7.0, 3.0, program="_init"),
        _span("hvd/compile/lower", T0 + 10.0, 1.0, program="jit(_init)"),
        _span("hvd/compile/cache_load", T0 + 11.0, 4.0, program="jit(_init)"),
        # _step: traced, lowered, compiled (the cache did not have it).
        _span("hvd/compile/trace", T0 + 20.0, 5.0, program="_step"),
        _span("hvd/compile/lower", T0 + 25.0, 2.0, program="jit(_step)"),
        _span("hvd/compile/backend_compile", T0 + 27.0, 8.0,
              program="jit(_step)"),
        _span("hvd/compile/backend_compile", T0 + 36.0, 0.5,
              program="jit(convert_element_type)"),
    ] + list(after_window)


AFTER = [
    # The traced run's second compile of the step, for its HLO text.
    _span("hvd/compile/trace", WINDOW + 21.0, 5.0, program="_step"),
    _span("hvd/compile/lower", WINDOW + 26.0, 2.0, program="jit(_step)"),
    _span("hvd/compile/cache_load", WINDOW + 28.0, 9.0, program="jit(_step)"),
    # One that straddles the window's start did not end before it.
    _span("hvd/compile/backend_compile", WINDOW - 0.1, 0.2, program="late"),
]

WANT = {"hvd_init_s": 2.0, "trace_lower_s": 11.0, "cache_load_s": 4.0,
        "backend_compile_s": 8.5, "compile_requests": 3.0}


@pytest.fixture
def readers():
    return spec.metric_readers()


@pytest.fixture
def program(monkeypatch):
    """Put a made-up record in the place of the process's own."""
    import horovod_tpu

    def put(record):
        monkeypatch.setattr(horovod_tpu, "spans", lambda: list(record))
    return put


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_on_a_made_up_record(readers, program, name):
    program(_record())
    assert readers[name].read(dict(RUN)) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_leaves_a_compile_after_the_window_out(readers, program, name):
    program(_record(AFTER))
    assert readers[name].read(dict(RUN)) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_finds_nothing_in_a_run_no_trainer_made(readers, program,
                                                       name):
    """The process's record is full (other tests filled it), but the
    run holds no ``setup_s`` or no ``init_s``: nothing is reported."""
    program(_record())
    assert readers[name].read({}) is None
    assert readers[name].read({"init_s": 1.5, "programs_compiled": 0,
                               "device": {}}) is None
    assert readers[name].read({"setup_s": 40.0}) is None


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_on_a_program_without_the_record(readers, monkeypatch, name):
    """The parent commit has no ``hvd.spans``: nothing, and no error."""
    import horovod_tpu
    monkeypatch.delattr(horovod_tpu, "spans")
    assert readers[name].read(dict(RUN)) is None


def test_hvd_init_s_prints_its_children_and_the_import(readers, program,
                                                       capsys):
    program(_record())
    readers["hvd_init_s"].read(dict(RUN))
    line = capsys.readouterr().out
    assert line.startswith("bench: hvd/init 2.000 s of init_s 6.000")
    for part in ("'hvd/init/rendezvous': 0.25", "'hvd/init/backend': 1.5",
                 "'hvd/init/runtime': 0.25", "'hvd/import': 2.5"):
        assert part in line
    # And where set-up went that no span saw, the longest stretch first.
    assert ("by the span before: [['hvd/compile/cache_load', 'jit(_init)', "
            "5.0], ['hvd/compile/backend_compile', "
            "'jit(convert_element_type)', 3.5], ") in line


@pytest.mark.parametrize("name,want", [
    ("negotiate_ms", 30.0), ("dispatch_ms", 400.0), ("wait_ms", 900.0)])
def test_the_eager_planes_span_readers(readers, name, want):
    """Three steps: the seconds inside a span name from one exchange to
    the next, a median over the steps; a span across a boundary is cut
    at it; nothing without the trace's program spans."""
    def step(at, negotiate, dispatch, wait):
        return [{"name": "hvd/exchange", "start": at, "end": at + 1.5},
                {"name": "hvd/negotiate", "start": at + 0.1,
                 "end": at + 0.1 + negotiate},
                {"name": "hvd/dispatch", "start": at + 0.2,
                 "end": at + 0.2 + dispatch / 2},
                {"name": "hvd/dispatch", "start": at + 1.0,
                 "end": at + 1.0 + dispatch / 2},
                {"name": "hvd/wait", "start": at + 0.3, "end": at + 0.3 + wait}]
    spans = (step(0.0, 0.02, 0.3, 0.9) + step(4.0, 0.03, 0.4, 1.0)
             + step(8.0, 0.05, 0.5, 0.8) + step(12.0, 9.0, 9.0, 9.0))
    # The second step's wait runs 0.1 s into the third: cut at 8.0.
    spans.append({"name": "hvd/wait", "start": 7.9, "end": 8.1})
    # 0.9 | 1.0 + 0.1 | 0.8 + 0.1 -> median 0.9 s of waiting a step.
    run = {"trace": {"program_spans": spans}}
    assert readers[name].read(run) == pytest.approx(want)
    assert readers[name].read({"trace": {"busy_s": 1.0}}) is None
    assert readers[name].read({"trace": {"program_spans": spans[:1]}}) is None
