"""The in-graph trainer: one process, the whole step one compiled
program of ``horovod_tpu.training`` on a ``build_mesh`` mesh.

Started by ``run.py`` under ``python -m horovod_tpu.runner.launch -np 1``.
Everything that names a size comes from the cell's files.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks import spec  # noqa: E402
from benchmarks.trainers import common  # noqa: E402
from benchmarks.trainers.common import info, span  # noqa: E402


def main(argv=None, platform: str = "tpu", root: str = spec.HERE):
    args = common.parse_args(argv)
    watch = common.CompileWatch()
    import horovod_tpu as hvd
    from horovod_tpu.common import compile_cache
    from horovod_tpu.parallel import build_mesh

    hvd.init()
    init_s = time.time() - args.t0
    cache_dir = compile_cache.enable()
    cell = spec.Cell(args.workload, root)
    device = common.require_device(platform, cell.chips)
    family = spec.load_family(cell.config)
    t = cell.traffic
    info("cell %s: config %s, traffic %s, %d x %d a chip, cache %s"
         % (cell.name, cell.config_name, cell.traffic_name,
            t["batch_per_chip"], t["seq_len"], cache_dir))

    # The cell's chips and no others, wherever it is started.
    mesh = build_mesh(t.get("mesh") or {"dp": cell.chips},
                      jax.devices()[:cell.chips])
    pool = common.batch_pool(family, cell, args.seed)
    model = family.ingraph(cell.config, mesh, pool[0])

    def place(batch):
        return jax.tree.map(
            lambda a: jax.device_put(a, model.batch_sharding), batch)

    parts = {}
    t0 = time.perf_counter()
    state = jax.block_until_ready(
        model.init(jax.random.PRNGKey(args.seed), place(pool[0])))
    parts["init_program_s"] = time.perf_counter() - t0
    n_params = sum(p.size for p in jax.tree.leaves(model.params(state)))
    info("%d parameters, state on %s" % (n_params, platform))

    t0 = time.perf_counter()
    ref_ok, _ = common.reference_check(family, cell, model.params(state),
                                       args.seed)
    parts["reference_check_s"] = time.perf_counter() - t0

    losses = []   # device scalars, fetched after the window
    n = 0

    def one_step(state):
        nonlocal n
        with span("input"):
            batch = place(pool[n % len(pool)])
        with span("step"):
            state, loss = model.step(state, batch)
        losses.append(loss)
        n += 1
        return state, loss

    warm = []
    for _ in range(t["warmup_steps"]):
        t0 = time.perf_counter()
        state, loss = one_step(state)
        float(loss)
        warm.append(time.perf_counter() - t0)
    parts["first_step_s"], parts["steady_step_s"] = warm[0], min(warm[1:])
    parts["warmup_s"] = sum(warm)

    requests_before = watch.requests
    setup_s = time.time() - args.t0
    step_s, trace = [], None
    w0 = time.perf_counter()
    if args.trace:
        # Every step fetched, for the spread of single steps; then a
        # few steps as the measured loop runs them, under the profiler.
        while time.perf_counter() - w0 < args.seconds:
            t0 = time.perf_counter()
            state, loss = one_step(state)
            with span("fetch"):
                float(loss)
            step_s.append(time.perf_counter() - t0)
        window_s, steps = time.perf_counter() - w0, len(step_s)
        tracer = common.Tracer(args.out)
        with tracer:
            with span("window"):
                for _ in range(t["traced_steps"]):
                    state, loss = one_step(state)
                with span("fetch"):
                    float(loss)
        trace = tracer.reduce(model.hlo_text(state, place(pool[0])))
    else:
        # Groups of ``fetch_every`` steps, the loss fetched at the end
        # of each as a loop that logs does; the window ends with the
        # fetch that ends the last whole group.
        steps = 0
        while True:
            for _ in range(t["fetch_every"]):
                state, loss = one_step(state)
            with span("fetch"):
                float(loss)
            steps += t["fetch_every"]
            window_s = time.perf_counter() - w0
            if window_s >= args.seconds:
                break
    window_compiles = watch.check_window(requests_before, strict=True)

    band_step = cell.workload["loss_band"]["step"]
    while n < band_step:
        state, loss = one_step(state)
    host_losses = [float(v) for v in jax.device_get(losses)]
    failed = int(np.sum(~np.isfinite(host_losses)))
    correct = (ref_ok and failed == 0
               and common.on_platform(state, platform)
               and common.loss_band_ok(cell, host_losses))
    device["memory_peak_bytes"] = common.peak_bytes()
    run = {
        "cell": cell.name, "seed": args.seed, "device": device,
        "correct": correct, "attempted": n, "failed": failed,
        "steps": steps, "window_s": window_s, "losses": host_losses,
        "setup_s": setup_s, "init_s": init_s, "setup_parts": parts,
        "compile_s": (parts["init_program_s"] + parts["first_step_s"]
                      - parts["steady_step_s"]),
        "programs_compiled": watch.programs_compiled,
        "compiled_s": watch.compiled, "loaded_s": watch.loaded,
        "window_compiles": window_compiles,
        "step_s": step_s, "traced_steps": t["traced_steps"],
        "trace": trace, "n_params": n_params,
        "n_devices_used": mesh.devices.size,
    }
    common.finish(args, cell, family, run, root)
    hvd.shutdown()
    return run


if __name__ == "__main__":
    main()
