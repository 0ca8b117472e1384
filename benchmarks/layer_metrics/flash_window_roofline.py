"""Share of the chip's peak that the window layers' two flash kernels
reach, in per cent: their required FLOPs at the BAND
(``flash_kernel_work`` of the cell's family: scores the band holds, not
the scores of the tiles walked, so masked scores and a kernel that
walked the triangle would only lower it, and it cannot read over 100)
over their device time in the traced steps times the peak.  The kernels
are the breakdown's groups whose path ends in ``hvd_flash_fwd_window``
or ``hvd_flash_bwd_window`` (the custom calls keep their ``op_name``),
each called once a window layer and step.

The reduction hands readers the ten groups with most self time; where
only one of the two kernels is among them the share is that kernel's
alone (its own work over its own time), and where neither is, 0, as the
other shares read off the ten groups do.  The full layer's kernels'
share is printed beside it on an information line.  None where the run
has no reduced trace."""

LAYER = "Kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "mfu"

WINDOW_KERNELS = ("hvd_flash_fwd_window", "hvd_flash_bwd_window")
FULL_KERNELS = ("hvd_flash_fwd", "hvd_flash_bwd")
WINDOW_KIND, FULL_KIND = "sliding_attention", "full_attention"


def kernel_of(group: str):
    """The last part of a breakdown key's path: ``<module path>
    [category]``."""
    return group.split(" [")[0].split("/")[-1]


def share(run: dict, kernels, kind: str):
    """``(per cent of the peak, seconds a call by kernel)`` of those of
    ``kernels`` that are among the trace's groups, called once a layer
    of ``kind`` and step; None where none is."""
    seconds = {}
    for group, s in run["trace"].get("device_ops") or []:
        if kernel_of(group) in kernels:
            seconds[kernel_of(group)] = seconds.get(kernel_of(group), 0.0) + s
    if not seconds:
        return None
    from benchmarks import peaks, spec
    cell = spec.Cell(run["cell"])
    family = spec.load_family(cell.config)
    t = cell.traffic
    work = family.flash_kernel_work(cell.config, t["batch_per_chip"],
                                    t["seq_len"])
    calls = run["traced_steps"] * sum(
        held == kind for held, _ in family.reference.layer_kinds(cell.config))
    peak = peaks.peak_of(run["device"]["kind"]).bf16_flops_per_s
    flops = calls * sum(work[k][0] for k in seconds)
    return (100.0 * flops / (sum(seconds.values()) * peak),
            {k: s / calls for k, s in seconds.items()})


def read(run: dict):
    if not run.get("trace"):
        return None
    window = share(run, WINDOW_KERNELS, WINDOW_KIND)
    if window is None:
        return 0.0
    full = share(run, FULL_KERNELS, FULL_KIND)
    from benchmarks.trainers.common import info
    info("flash kernels, ms a call: window %s at %.1f %% of the peak; full "
         "%s" % ({k: round(1e3 * s, 3) for k, s in window[1].items()},
                 window[0],
                 "not among the ten groups" if full is None else
                 "%s at %.1f %%" % ({k: round(1e3 * s, 3)
                                     for k, s in full[1].items()}, full[0])))
    return window[0]
