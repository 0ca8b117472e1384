"""The five readers that add ``setup_s`` up from the program's set-up
spans (PR 34), on a made-up record: the device client's start, the
imports, the shardings, the first calls and what no span covers."""

import json
import time

import pytest

from benchmark_tiny import make_root, spec

from benchmarks.layer_metrics import _program, _setup

T0 = 2_000_000.0          # the command's start on the made-up wall clock
RUN = {"init_s": 10.0, "setup_s": 50.0,
       "setup_parts": {"steady_step_s": 0.25}}
WINDOW = T0 + RUN["setup_s"]


def _span(name, start, seconds, parent=None, **args):
    return {"name": name, "start": start, "end": start + seconds,
            "thread": "MainThread", "parent": parent, "args": args}


def _start_up(at, module=True):
    """A run's ``hvd/import`` and ``hvd/init``: imported at ``at`` + 1,
    ``hvd.init()`` from ``at`` + 4 to ``at`` + 10, 5 s of it the device
    client."""
    named = {"module": "horovod_tpu"} if module else {}
    spans = [
        _span("hvd/import", at + 1.0, 2.5, **named),
        _span("hvd/init/rendezvous", at + 4.0, 0.25, "hvd/init"),
        _span("hvd/init/backend", at + 9.25, 0.5, "hvd/init"),
        _span("hvd/init/runtime", at + 9.75, 0.25, "hvd/init"),
        _span("hvd/init", at + 4.0, 6.0)]
    if module:
        spans.insert(2, _span("hvd/init/device_client", at + 4.25, 5.0,
                              "hvd/init", platform="tpu", devices=1))
    return spans


# What the benchmark itself compiles (the reference check): no parent.
REFERENCE = [
    _span("hvd/compile/trace", T0 + 26.0, 2.0, program="loss"),
    _span("hvd/compile/backend_compile", T0 + 28.0, 2.0,
          program="jit(loss)"),
]


def _record():
    first = "hvd/program/first_call"
    return (
        # An earlier run of the same process, whole.
        _start_up(T0 - 500.0)
        + [_span("hvd/step/shardings", T0 - 485.0, 7.0, program="_init"),
           _span(first, T0 - 470.0, 30.0, program="_init", kind="init")]
        + _start_up(T0)
        + [
            # Before hvd.init() returned: not between it and the window.
            _span("hvd/step/shardings", T0 + 2.0, 0.5, program="early"),
            _span(first, T0 + 3.0, 0.5, program="early", kind="init"),
            # The late imports, one inside the other: counted once.
            _span("hvd/import", T0 + 12.0, 1.0, module="horovod_tpu.models"),
            _span("hvd/import", T0 + 11.0, 3.0,
                  module="horovod_tpu.training"),
            # The state's layout, its abstract trace inside it.
            _span("hvd/compile/trace", T0 + 15.1, 1.5, "hvd/step/shardings",
                  program="_init"),
            _span("hvd/step/shardings", T0 + 15.0, 2.0, program="_init"),
            # _init's first call: 8 s, 5 of them compile spans.
            _span("hvd/compile/trace", T0 + 17.0, 1.0, first,
                  program="_init"),
            _span("hvd/compile/lower", T0 + 18.0, 1.0, first,
                  program="jit(_init)"),
            _span("hvd/compile/cache_load", T0 + 19.0, 3.0, first,
                  program="jit(_init)"),
            _span(first, T0 + 17.0, 8.0, program="_init", kind="init")]
        + REFERENCE
        + [
            # The step's: 10 s, 7 in compile spans (trace and lower
            # share half a second).
            _span("hvd/compile/trace", T0 + 32.0, 4.0, first,
                  program="step_fn"),
            _span("hvd/compile/lower", T0 + 35.5, 1.5, first,
                  program="jit(step_fn)"),
            _span("hvd/compile/cache_load", T0 + 37.0, 2.0, first,
                  program="jit(step_fn)"),
            _span(first, T0 + 32.0, 10.0, program="step_fn", kind="step"),
            # Open when the window opened: its half second before it is
            # covered, and it is nobody's whole span.
            _span("hvd/import", WINDOW - 0.5, 1.0, module="late"),
            # Inside the window (the traced run's work after it).
            _span("hvd/step/shardings", WINDOW + 1.0, 1.0, program="_init"),
            _span(first, WINDOW + 2.0, 4.0, program="other", kind="step"),
            _span("hvd/compile/cache_load", WINDOW + 3.0, 2.0, first,
                  program="jit(other)"),
        ])


def _parents_record():
    """The same run by a program from before PR 34: an ``hvd/import``
    that names no module, four children of ``hvd/init``, the compile
    spans with no parent."""
    return (_start_up(T0, module=False) + REFERENCE
            + [_span("hvd/compile/trace", T0 + 32.0, 4.0, program="step_fn"),
               _span("hvd/compile/cache_load", T0 + 37.0, 2.0,
                     program="jit(step_fn)")])


# 40 s between hvd.init() and the window; spans cover 11-14, 15-25,
# 26-30, 32-42 and 49.5-50: 27.5 of them.
WANT = {"device_client_s": 5.0, "import_s": 5.5, "shardings_s": 2.0,
        "first_run_s": 6.0, "setup_unspanned_s": 12.5}


@pytest.fixture
def readers():
    return spec.metric_readers()


@pytest.fixture
def program(monkeypatch):
    import horovod_tpu

    def put(record):
        monkeypatch.setattr(horovod_tpu, "spans", lambda: list(record))
    return put


@pytest.mark.parametrize("name", sorted(WANT))
def test_setup_reader(readers, program, monkeypatch, name):
    """Each reader: on the made-up record (spans before ``hvd.init()``,
    inside the window and of an earlier run are left out, overlapping
    spans are counted once); nothing on the parent's record, on a run
    no trainer made, or on a program without ``hvd.spans``."""
    program(_record())
    assert readers[name].read(dict(RUN)) == pytest.approx(WANT[name])
    assert readers[name].read({"setup_s": 50.0}) is None
    program(_parents_record())
    assert readers[name].read(dict(RUN)) is None
    import horovod_tpu
    monkeypatch.delattr(horovod_tpu, "spans")
    assert readers[name].read(dict(RUN)) is None


def test_the_parts_add_up_to_setup_s(readers, program):
    """``init_s`` + what the program's spans cover after ``hvd.init()``
    + ``setup_unspanned_s`` is ``setup_s``."""
    program(_record())
    covered = _program.covered_s(_setup.after_init(dict(RUN)))
    assert covered == pytest.approx(27.5)
    assert RUN["init_s"] + covered \
        + readers["setup_unspanned_s"].read(dict(RUN)) \
        == pytest.approx(RUN["setup_s"])


def test_the_information_lines(readers, program, capsys):
    program(_record())
    for name in sorted(WANT):
        readers[name].read(dict(RUN))
    out = capsys.readouterr().out
    assert ("bench: device client 5.000 s of hvd/init 6.000: "
            "{'platform': 'tpu', 'devices': 1}") in out
    assert ("[['horovod_tpu', 2.5], ['horovod_tpu.models', 1.0], "
            "['horovod_tpu.training', 3.0]]; after hvd.init() 3.000 s") in out
    assert "[['_init', 2.0, 1.5]]" in out
    assert ("[['_init', 'init', 8.0, 5.0], ['step_fn', 'step', 10.0, 7.0]]; "
            "steady_step_s 0.25") in out
    assert ("bench: setup_s 50.000 = init_s 10.000 + 27.500 in the "
            "program's spans + 12.500 in none") in out


def test_a_tiny_traced_cell_reports_the_five_and_they_add_up(tmp_path,
                                                             capsys):
    """The in-graph trainer on the CPU, traced: the real program's
    record under the real readers."""
    from benchmarks.trainers import ingraph
    name = make_root(str(tmp_path), "gpt", "ingraph", mesh={"dp": 1})
    out = tmp_path / "out"
    out.mkdir()
    run = ingraph.main(
        ["--workload", name, "--seed", "5", "--seconds", "0.3", "--trace",
         "1", "--t0", repr(time.time()), "--out", str(out)],
        platform="cpu", root=str(tmp_path))
    got = json.loads((out / "result.json").read_text())["metrics"]
    # The test's process imported the program before this run's start,
    # perhaps long before: import_s is there only where it did not.
    assert set(WANT) - {"import_s"} <= set(got)
    assert 0 <= got["device_client_s"]["value"] <= got["hvd_init_s"]["value"]
    # The init and the step program, each once.
    line = [l for l in capsys.readouterr().out.splitlines()
            if l.startswith("bench: first calls")][-1]
    assert "['_init', 'init'," in line and "['step_fn', 'step'," in line
    covered = _program.covered_s(_setup.after_init(run))
    assert run["init_s"] + covered + got["setup_unspanned_s"]["value"] \
        == pytest.approx(run["setup_s"], abs=1e-6)
    # The first calls are inside the clocks the trainer took around them.
    assert got["first_run_s"]["value"] \
        <= run["setup_parts"]["init_program_s"] \
        + run["setup_parts"]["first_step_s"]
