"""What the readers of a named kernel's share of the chip's peak share.

The reduction hands readers the ten groups with most self time; a
custom call keeps its kernel's name as the last part of its group's
path.  A kernel that is not among the ten has no time to read."""


def kernel_of(group: str) -> str:
    """The last part of a breakdown key's path: ``<module path>
    [category]``."""
    return group.split(" [")[0].split("/")[-1]


def seconds_by_kernel(run: dict, kernels) -> dict:
    """Self seconds in the traced steps of those of ``kernels`` that are
    among the trace's groups, by kernel."""
    seconds = {}
    for group, s in run["trace"].get("device_ops") or []:
        if kernel_of(group) in kernels:
            seconds[kernel_of(group)] = seconds.get(kernel_of(group), 0.0) + s
    return seconds


def share_of_peak(run: dict, kernels, work_of: str):
    """``(per cent of the matrix unit's peak, milliseconds a call by
    kernel)`` of those of ``kernels`` among the trace's groups, each
    called once a layer and step: their required FLOPs (the cell's
    family's ``work_of`` function: ``{kernel: (FLOPs, bytes)}`` of one
    call) over their device time times the peak.  None where none is
    among the groups, or where the program's family has no such count
    (a commit before it)."""
    seconds = seconds_by_kernel(run, kernels)
    if not seconds:
        return None
    from benchmarks import peaks, spec
    cell = spec.Cell(run["cell"])
    family = spec.load_family(cell.config)
    if not hasattr(family, work_of):
        return None
    t = cell.traffic
    work = getattr(family, work_of)(cell.config, t["batch_per_chip"],
                                    t["seq_len"])
    calls = run["traced_steps"] * cell.config["num_hidden_layers"]
    peak = peaks.peak_of(run["device"]["kind"]).bf16_flops_per_s
    flops = calls * sum(work[k][0] for k in seconds)
    return (100.0 * flops / (sum(seconds.values()) * peak),
            {k: round(1e3 * s / calls, 3) for k, s in seconds.items()})
