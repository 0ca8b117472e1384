"""Seconds of rank 0's first calls that no compile span covers: for each
``hvd/program/first_call`` span before the window (the init and the
step program of ``horovod_tpu.training``), its seconds less what the
``hvd/compile/*`` spans inside it cover; summed.  What is left is the
executable's load onto the chip and its first run, of which one steady
step is compute (``steady_step_s``, printed beside)."""

from benchmarks.layer_metrics import _setup

LAYER = "Compile"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "setup_s"


def read(run: dict):
    at = _setup.instants(run)
    if at is None:
        return None
    calls = _setup.named(run, _setup.FIRST_CALL, at[1], at[2])
    if not calls:
        return None
    rows = [(s["args"].get("program"), s["args"].get("kind"),
             s["end"] - s["start"], _setup.compile_s_inside(run, s))
            for s in calls]
    print("bench: first calls [program, kind, seconds, of which compile "
          "spans]: %s; steady_step_s %s"
          % ([[program, kind, round(seconds, 3), round(compiling, 3)]
              for program, kind, seconds, compiling in rows],
             (run.get("setup_parts") or {}).get("steady_step_s")),
          flush=True)
    return sum(seconds - compiling for _, _, seconds, compiling in rows)
