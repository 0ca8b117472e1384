"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The parent never imports JAX (a chip belongs to one process at a time).
It reads the cell's files, starts the trainer the traffic file names
under ``python -m horovod_tpu.runner.launch -np <processes>``, echoes
what the workers print, kills the whole group on a limit, and prints
rank 0's result as the last line: one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
when traced).  Anything else (no chip, a failed worker, no result)
exits non-zero and prints no result.
"""

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

from benchmarks import spec  # noqa: E402

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# The contract allows a first run, which compiles, 1200 s.
LIMIT_S = 1150.0
OUT_ROOT = os.path.join(REPO, "chiprun_out", "benchmarks")


def _kill_group(proc):
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        pass


def child_env(environ=os.environ) -> dict:
    """The workers' environment: the repo importable, and the compile
    cache where ``JAX_COMPILATION_CACHE_DIR`` says or, where nothing
    says, at ``<checkout>/.jax_cache`` (the program's own rule; a fixed
    path, because the path is part of the cache's key)."""
    env = dict(environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env["PYTHONPATH"] \
        if env.get("PYTHONPATH") else REPO
    env[CACHE_ENV] = env.get(CACHE_ENV) or os.path.join(REPO, ".jax_cache")
    return env


def main(argv=None) -> int:
    t0 = time.time()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="default: the manifest's run_seconds")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    seconds = args.seconds if args.seconds is not None else \
        spec.load_manifest()["run_seconds"]
    cell = spec.Cell(args.workload)

    out = os.path.join(OUT_ROOT, "%s-seed%d-trace%d"
                       % (cell.name, args.seed, args.trace))
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    cmd = [sys.executable, "-m", "horovod_tpu.runner.launch",
           "-np", str(cell.processes),
           sys.executable, cell.trainer_path,
           "--workload", cell.name, "--seed", str(args.seed),
           "--seconds", str(seconds), "--trace", str(args.trace),
           "--t0", repr(t0), "--out", out]
    print("bench: starting %s" % " ".join(cmd), flush=True)
    proc = subprocess.Popen(cmd, cwd=REPO, env=child_env(),
                            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                            text=True, start_new_session=True)
    timer = threading.Timer(LIMIT_S, _kill_group, args=(proc,))
    timer.daemon = True
    timer.start()
    try:
        for line in proc.stdout:
            sys.stdout.write(line)
            sys.stdout.flush()
        rc = proc.wait()
    finally:
        timer.cancel()
        _kill_group(proc)
    result_path = os.path.join(out, "result.json")
    if rc != 0 or not os.path.exists(result_path):
        print("bench: FAILED: launcher exit code %s, result %s"
              % (rc, "present" if os.path.exists(result_path) else "missing"),
              flush=True)
        return 1
    with open(result_path) as f:
        result = json.load(f)
    print("bench: whole command %.1f s" % (time.time() - t0), flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
