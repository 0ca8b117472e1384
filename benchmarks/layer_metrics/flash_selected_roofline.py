"""Share of the chip's peak that the two flash kernels with a selection
reach, in per cent: their required FLOPs at the SELECTED pairs
(``flash_kernel_work`` of the cell's family: the scores the selection
keeps, not the scores of the tiles walked, so a walked triangle only
lowers it and it cannot read over 100) over their device time in the
traced steps times the peak.  The kernels are the breakdown's groups
whose path ends in ``hvd_flash_fwd_selected`` or
``hvd_flash_bwd_selected``, each called once a layer and step.

The reduction hands readers the ten groups with most self time; where
only one of the two kernels is among them the share is that kernel's
alone, and where neither is, 0, as ``flash_window_roofline`` reads.
None where the run has no reduced trace."""

LAYER = "Kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "mfu"

KERNELS = ("hvd_flash_fwd_selected", "hvd_flash_bwd_selected")


def read(run: dict):
    if not run.get("trace"):
        return None
    from benchmarks.layer_metrics import _kernels
    share = _kernels.share_of_peak(run, KERNELS, "flash_kernel_work")
    if share is None:
        return 0.0
    from benchmarks.trainers.common import info
    info("selected flash kernels, ms a call: %s at %.1f %% of the peak"
         % (share[1], share[0]))
    return share[0]
