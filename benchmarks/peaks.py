"""Published peaks of the chips the benchmark may run on.

One table, keyed by a substring of ``device_kind``.  A kind that is not
listed is an error, never a default: utilization against a guessed peak
is a wrong number under a right name.

Source of the v5e row: Google Cloud documentation, "TPU v5e" (197
TFLOP/s bf16, 16 GB of HBM2e at 819 GB/s a chip).  The other rows are
the same documentation's pages for those chips.  The bf16 column is
copied from ``bench.py`` ``PEAK_BF16_TFLOPS`` (PERF.md section 7 lists
the original for deletion).
"""

from typing import NamedTuple


class Peak(NamedTuple):
    bf16_flops_per_s: float
    hbm_bytes_per_s: float


PEAKS = (
    ("v6e", Peak(918e12, 1640e9)),
    ("v6", Peak(918e12, 1640e9)),
    ("v5p", Peak(459e12, 2765e9)),
    ("v5e", Peak(197e12, 819e9)),
    ("v5litepod", Peak(197e12, 819e9)),
    ("v5 lite", Peak(197e12, 819e9)),
    ("v4", Peak(275e12, 1200e9)),
)


def peak_of(device_kind: str) -> Peak:
    kind = device_kind.lower()
    for key, peak in PEAKS:
        if key in kind:
            return peak
    raise ValueError(
        "no peak listed for device_kind %r; add it to benchmarks/peaks.py "
        "with its source" % device_kind)
