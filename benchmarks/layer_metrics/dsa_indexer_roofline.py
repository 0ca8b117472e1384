"""Share of the chip's peak that the indexer's two kernels reach, in
per cent: the required FLOPs of the index scores at every causal pair
and of the alignment loss (``indexer_kernel_work`` of the cell's
family: the scores' backward at every causal pair and the main
attention's scores at the selected pairs; what either spends making
scores a second time, counting and comparing is not counted, so it
cannot read over 100) over their device time in the traced steps times
the peak.  Both are matrix products by their count, so the peak is the
matrix unit's.  The kernels are the breakdown's groups whose path ends
in ``hvd_dsa_select`` or ``hvd_dsa_indexer_loss``, each called once a
layer and step.

The reduction hands readers the ten groups with most self time; where
only one of the two kernels is among them the share is that kernel's
alone, and where neither is, 0, as ``flash_window_roofline`` reads.
None where the run has no reduced trace."""

LAYER = "Kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "mfu"

KERNELS = ("hvd_dsa_select", "hvd_dsa_indexer_loss")


def read(run: dict):
    if not run.get("trace"):
        return None
    from benchmarks.layer_metrics import _kernels
    share = _kernels.share_of_peak(run, KERNELS, "indexer_kernel_work")
    if share is None:
        return 0.0
    from benchmarks.trainers.common import info
    info("indexer kernels, ms a call: %s at %.1f %% of the peak"
         % (share[1], share[0]))
    return share[0]
