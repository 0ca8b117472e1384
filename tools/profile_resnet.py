"""One-shot ResNet-50 step profile for the MFU ceiling analysis.

Captures, in a single TPU session (one compile serves every item):

  * XLA cost analysis of the jitted train step (FLOPs, bytes
    accessed, arithmetic intensity) — analytic fallback when the
    backend exposes none, clearly labeled;
  * an HLO-op histogram of the optimized module (convolution /
    fusion / reduce / copy counts) — copies and converts are the
    usual MFU leaks;
  * measured step time -> achieved TFLOP/s and MFU vs the chip peak;
  * an HBM roofline keyed on the chip generation;
  * optionally a profiler trace (--trace DIR, view in XProf).

The train step comes from bench.build_resnet_train_step, so the
profile measures EXACTLY the program bench.py scores.

Usage (on a host with the TPU attached):
    python tools/profile_resnet.py --batch-size 128 --iters 30
    python tools/profile_resnet.py --batch-size 128 --trace /tmp/tb
"""

import argparse
import collections
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# HBM bandwidth GB/s per chip generation (public cloud.google.com/tpu
# numbers), keyed on device_kind substrings like bench.PEAK_BF16_TFLOPS.
HBM_GBPS = [
    ("v6e", 1640.0), ("v6", 1640.0),
    ("v5p", 2765.0),
    ("v5e", 819.0), ("v5litepod", 819.0), ("v5 lite", 819.0),
    ("v4", 1228.0),
    ("v3", 900.0),
    ("v2", 700.0),
]


def hbm_gbps(device) -> float:
    kind = getattr(device, "device_kind", "").lower()
    for key, bw in HBM_GBPS:
        if key in kind:
            return bw
    return 0.0


def summarize_compiled(compiled, device,
                       analytic_flops: float = 0.0) -> dict:
    """Per-HLO summary of a compiled step: XLA cost analysis (FLOPs,
    bytes accessed, arithmetic intensity), the HLO op histogram
    (convolutions / fusions / copies / transposes — the usual MFU
    leaks), and the HBM-roofline step time the bytes imply.  Shared by
    the profiler CLI and bench.py's HOROVOD_BENCH_PROFILE=1 lane, so
    the MFU-ceiling claim rides the artifact instead of prose."""
    flops, nbytes, flops_source = 0.0, 0.0, "xla_cost_analysis"
    report = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        flops = float(ca.get("flops", 0.0))
        nbytes = float(ca.get("bytes accessed", 0.0))
    except Exception as e:
        report["cost_analysis_error"] = repr(e)[:200]
    if not flops and analytic_flops:
        flops = analytic_flops
        flops_source = "analytic"
    report.update({
        "flops_per_step": flops or None,
        "flops_source": flops_source,
        "bytes_accessed_per_step": nbytes or None,
        "arithmetic_intensity": round(flops / nbytes, 1)
        if nbytes and flops else None,
    })
    try:
        hlo = compiled.as_text()
        hist = collections.Counter()
        for m in re.finditer(r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*"
                             r"[\w\[\],{}\d\s]*?\s([a-z\-]+)\(",
                             hlo, re.M):
            hist[m.group(1)] += 1
        report["hlo_op_histogram"] = dict(hist.most_common(20))
        report["hlo_copies"] = hist.get("copy", 0)
        report["hlo_transposes"] = hist.get("transpose", 0)
        report["hlo_convs"] = (hist.get("convolution", 0) +
                               hist.get("conv", 0))
        report["hlo_fusions"] = hist.get("fusion", 0)
    except Exception as e:
        report["hlo_error"] = repr(e)[:200]
    bw = hbm_gbps(device)
    report["hbm_gbps_assumed"] = bw or None
    # Step time implied by bytes at the chip's HBM bandwidth: if close
    # to the measured step, the step is bandwidth-bound and MFU's
    # ceiling is the roofline, not scheduling.
    report["hbm_bound_step_ms"] = round(nbytes / (bw * 1e9) * 1e3, 2) \
        if nbytes and bw else None
    return report


def compiled_step_summary(jitted, args, device,
                          analytic_flops: float = 0.0) -> dict:
    """Lower + compile a jitted step and summarize it (bench.py entry
    point; the compile rides the persistent XLA cache so a bench run
    that already compiled the step pays nothing extra)."""
    return summarize_compiled(jitted.lower(*args).compile(), device,
                              analytic_flops)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batch-size", type=int, default=128)
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--iters", type=int, default=30)
    p.add_argument("--warmup", type=int, default=5)
    p.add_argument("--trace", type=str, default=None,
                   help="capture a jax.profiler trace into this dir")
    p.add_argument("--cpu", action="store_true",
                   help="force CPU (pipeline debugging)")
    args = p.parse_args()

    import jax
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")

    from bench import (build_resnet_train_step, peak_bf16_tflops,
                       resnet50_analytic_flops)
    from horovod_tpu.common import compile_cache
    compile_cache.enable()

    dev = jax.devices()[0]
    print(f"device: {dev.device_kind} ({dev.platform})")

    (train_step, params, batch_stats, opt_state, x,
     labels) = build_resnet_train_step(args.batch_size,
                                       args.image_size, 1000)

    print("lowering/compiling...", flush=True)
    t0 = time.perf_counter()
    lowered = train_step.lower(params, batch_stats, opt_state, x,
                               labels)
    compiled = lowered.compile()
    print(f"compile: {time.perf_counter() - t0:.1f}s", flush=True)

    # --- cost analysis + HLO histogram (shared with bench.py's
    # HOROVOD_BENCH_PROFILE=1 lane) ---------------------------------------
    report = summarize_compiled(
        compiled, dev, resnet50_analytic_flops(args.batch_size))
    report["batch_size"] = args.batch_size
    flops = report.get("flops_per_step") or 0.0

    # --- timed run (drive the AOT executable: calling the jit wrapper
    # would retrace + recompile a second time) ----------------------------
    def run(n, p_, bs_, os_):
        loss = None
        for _ in range(n):
            p_, bs_, os_, loss = compiled(p_, bs_, os_, x, labels)
        if loss is not None:
            float(loss)
        return p_, bs_, os_

    params, batch_stats, opt_state = run(args.warmup, params,
                                         batch_stats, opt_state)
    if args.trace:
        import jax.profiler
        jax.profiler.start_trace(args.trace)
    t0 = time.perf_counter()
    params, batch_stats, opt_state = run(args.iters, params,
                                         batch_stats, opt_state)
    dt = time.perf_counter() - t0
    if args.trace:
        jax.profiler.stop_trace()
        report["trace_dir"] = args.trace

    step_s = dt / args.iters
    # --cpu debugs the pipeline; a CPU has no peak to divide by.
    peak = None if args.cpu else peak_bf16_tflops(dev)
    achieved = flops / step_s / 1e12
    report.update({
        "step_ms": round(step_s * 1e3, 2),
        "images_per_sec": round(args.batch_size / step_s, 1),
        "achieved_tflops": round(achieved, 1),
        "peak_bf16_tflops": peak,
        "mfu": round(achieved / peak, 4) if peak else None,
    })
    print(json.dumps(report, indent=1))


if __name__ == "__main__":
    main()
