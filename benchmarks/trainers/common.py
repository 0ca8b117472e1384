"""What both trainers do the same way: refuse anything but the chip,
watch JAX's compilations, compare with the reference, trace a few
steps, and hand rank 0's result to the parent.

The trainers are the benchmark's own files: spans, clocks and counters
are taken here, around the calls into the program.
"""

import argparse
import glob
import json
import os
import shutil

import jax
import numpy as np

from benchmarks import peaks, spec, trace_reduce
from benchmarks.reference import common as reference

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
# JAX stores no compilation quicker than this in its persistent cache,
# so a quicker one says nothing about the cache.
STORED_COMPILE_S = 1.0


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--t0", type=float, required=True,
                    help="time.time() at the start of the command")
    ap.add_argument("--out", required=True, help="the run's directory")
    return ap.parse_args(argv)


def info(msg: str):
    print("bench: " + msg, flush=True)


class CompileWatch:
    """Counts this process's backend compilations from JAX's monitoring
    events.  A request the persistent cache served records a hit just
    before its (short) ``backend_compile_duration``; that one is a load,
    not a compilation."""

    def __init__(self):
        self.compiled = []   # seconds of each real compilation
        self.loaded = []     # seconds of each load from the cache
        self.all = []        # both, in the order they came
        self._hit = False
        from jax import monitoring
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event, **_):
        if event == CACHE_HIT:
            self._hit = True

    def _on_duration(self, event, seconds, **_):
        if event == BACKEND_COMPILE:
            (self.loaded if self._hit else self.compiled).append(seconds)
            self.all.append(seconds)
            self._hit = False

    @property
    def requests(self) -> int:
        return len(self.compiled) + len(self.loaded)

    @property
    def programs_compiled(self) -> int:
        return sum(s >= STORED_COMPILE_S for s in self.compiled)

    def check_window(self, requests_before: int, strict: bool) -> int:
        """The requests since ``requests_before``; raises if the window
        compiled.  ``strict``: any request at all.  Otherwise only one
        of ``STORED_COMPILE_S`` or more: the eager plane compiles a small
        program for every new grouping of tensors it fuses, as it goes,
        and that is the system's steady behaviour, counted and shown."""
        inside = self.all[requests_before:]
        if (inside if strict else
                [s for s in inside if s >= STORED_COMPILE_S]):
            raise RuntimeError(
                "%d compilation requests inside the measured window, the "
                "longest %.2f s" % (len(inside), max(inside)))
        return len(inside)


def require_device(platform: str, chips: int) -> dict:
    """The device line of the result; raises unless JAX holds ``chips``
    devices of ``platform`` whose kind has a listed peak."""
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != platform:
        raise RuntimeError("the benchmark needs a %s device, JAX gave %s "
                           "(%s)" % (platform, dev.platform,
                                     dev.device_kind))
    if len(devices) < chips:
        raise RuntimeError("the cell needs %d chips, JAX sees %d"
                           % (chips, len(devices)))
    if platform == "tpu":
        peaks.peak_of(dev.device_kind)
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def peak_bytes() -> int:
    """The chip's peak: the arrays' peak and, beside it, what the TPU
    runtime keeps reserved for the temporaries of the loaded programs
    (``peak_bytes_in_use`` counts no program's scratch; the two regions
    are disjoint and add up to ``bytes_limit`` with the free block)."""
    def peak(device):
        stats = device.memory_stats() or {}
        return int(stats.get("peak_bytes_in_use", 0)
                   + stats.get("peak_bytes_reserved", 0))
    return max(peak(d) for d in jax.local_devices())


def on_platform(tree, platform: str) -> bool:
    return all(d.platform == platform
               for leaf in jax.tree.leaves(tree) for d in leaf.devices())


def batch_pool(family, cell: spec.Cell, seed: int, rank: int = 0):
    """The pool of host batches one process feeds, from the seed (and
    the rank): each the batch of all the chips the process drives."""
    rng = np.random.default_rng([seed, rank])
    t = cell.traffic
    batch = t["batch_per_chip"] * cell.chips // cell.processes
    return [family.host_batch(cell.config, batch, t["seq_len"], rng)
            for _ in range(t["pool_batches"])]


def reference_check(family, cell: spec.Cell, params, seed: int):
    """Correctness condition (1): on 2 seeded sequences at the cell's
    length, loss and three gradient leaves against the plain
    reference."""
    rng = np.random.default_rng([seed, 1 << 20])
    batch = family.host_batch(cell.config, 2, cell.traffic["seq_len"], rng)
    ok, report = reference.compare(
        family.system_loss(cell.config), family.reference_loss(cell.config),
        params, batch, cell.config["check_leaves"])
    info("reference check %s: %s" % ("ok" if ok else "FAILED",
                                     json.dumps(report)))
    return ok, report


def loss_band_ok(cell: spec.Cell, losses) -> bool:
    """Correctness condition (2), the band: the loss after
    ``loss_band.step`` steps lies where seeds 0 to 2 put it."""
    band = cell.workload["loss_band"]
    at = losses[band["step"] - 1]
    if band["low"] is None:
        info("loss after %d steps %.4f; no band recorded yet"
             % (band["step"], at))
        return True
    ok = band["low"] <= at <= band["high"]
    info("loss after %d steps %.4f, band [%.4f, %.4f]: %s"
         % (band["step"], at, band["low"], band["high"],
            "ok" if ok else "OUTSIDE"))
    return ok


class Tracer:
    """Rank 0's profiler trace of a few steady steps."""

    def __init__(self, out_dir: str):
        self.dir = os.path.join(out_dir, "trace")

    def __enter__(self):
        shutil.rmtree(self.dir, ignore_errors=True)
        # The device's operations and the benchmark's own spans; not
        # every Python call, which an op-by-op loop makes by the million.
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=options)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()

    def reduce(self, hlo_text: str = "", keep_events: int = 400):
        """The reduced trace (None if it holds no device operation).
        ``hlo_text`` is the compiled text of the traced program, for
        the module paths.  Leaves a description and the first events
        beside it and removes the raw trace, which is tens of
        megabytes."""
        paths = glob.glob(os.path.join(self.dir, "plugins", "profile",
                                       "*", "*.xplane.pb"))
        if not paths:
            return None
        out_dir = os.path.dirname(self.dir)
        with open(os.path.join(out_dir, "trace_planes.txt"), "w") as f:
            f.write("\n".join(trace_reduce.describe(paths[0])) + "\n")
        events = trace_reduce.load_events(
            paths[0], trace_reduce.op_names(hlo_text))
        in_window = [s for s in events["host"]
                     if s["name"] == trace_reduce.WINDOW_SPAN]
        first = in_window[0]["start_ns"] if in_window else 0.0
        sample = {"device": [e for e in events["device"]
                             if e["start_ns"] >= first][:keep_events],
                  "host": [s for s in events["host"]
                           if s["start_ns"] >= first][:keep_events]}
        with open(os.path.join(out_dir, "trace_events.json"), "w") as f:
            json.dump(sample, f)
        shutil.rmtree(self.dir, ignore_errors=True)
        return trace_reduce.reduce(events)


def span(name: str):
    return jax.profiler.TraceAnnotation(trace_reduce.SPAN_PREFIX + name)


def finish(args, cell: spec.Cell, family, run: dict, root: str):
    """Rank 0: work out the metrics of this run, print what is
    information on earlier lines, and leave the result where the parent
    reads it."""
    t = cell.traffic
    steps, window_s = run["steps"], run["window_s"]
    global_batch = t["batch_per_chip"] * cell.chips
    samples_per_s_chip = global_batch * steps / window_s / cell.chips
    device = run["device"]
    flops = family.flops_per_step(cell.config, global_batch, t["seq_len"])
    if device["platform"] == "tpu":
        peak = peaks.peak_of(device["kind"]).bf16_flops_per_s
        run["mfu"] = 100.0 * flops * steps / window_s / (cell.chips * peak)
    else:
        run["mfu"] = None  # a rehearsal off the chip has no utilization
    run["samples_per_s_chip"] = samples_per_s_chip
    info("%d steps in %.3f s; %.1f tokens/s/chip; %.3e required FLOPs a "
         "step; global batch %d x %d"
         % (steps, window_s, samples_per_s_chip * t["seq_len"], flops,
            global_batch, t["seq_len"]))
    info("losses of the first 32 steps: %s"
         % ["%.4f" % v for v in run["losses"][:32]])
    info("setup_s %.2f = init_s %.2f + %s"
         % (run["setup_s"], run["init_s"], json.dumps(run["setup_parts"])))
    info("compilations: %d of %.0f s or more, %d in all, %d loads from "
         "the cache %s; %d requests inside the window"
         % (run["programs_compiled"], STORED_COMPILE_S,
            len(run["compiled_s"]), len(run["loaded_s"]),
            ["%.1f" % s for s in run["loaded_s"] if s >= 0.5],
            run["window_compiles"]))

    if args.trace:
        metrics = spec.read_layer_metrics(run, cell.name, root=root)
        if run.get("trace"):
            device["busy_s"] = run["trace"]["busy_s"]
            device["window_s"] = run["trace"]["window_s"]
    else:
        manifest = spec.load_manifest()
        metrics = {m["name"]: {"value": float(run[m["name"]]),
                               "unit": m["unit"]}
                   for m in spec.metrics_of_cell(manifest, "end_to_end",
                                                 cell.name)
                   if run.get(m["name"]) is not None}
    result = {"correct": bool(run["correct"]), "attempted": run["attempted"],
              "failed": run["failed"], "metrics": metrics, "device": device}
    if args.trace and run.get("trace"):
        result["breakdown"] = {"device_ops": run["trace"]["device_ops"],
                               "idle_gaps": run["trace"]["idle_gaps"]}
        info("five longest idle gaps: %s"
             % json.dumps(run["trace"]["longest_gaps"]))
    with open(os.path.join(args.out, "run.json"), "w") as f:
        json.dump({k: v for k, v in run.items() if k != "trace"}, f,
                  default=float)
    tmp = os.path.join(args.out, "result.json.tmp")
    with open(tmp, "w") as f:
        json.dump(result, f)
    os.replace(tmp, os.path.join(args.out, "result.json"))
    info("result written: correct=%s" % result["correct"])
    return result
