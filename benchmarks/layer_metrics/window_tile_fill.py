"""Scores inside a window layer's band over the scores of the tiles its
flash kernels walk, in per cent: the program's gauge
``hvd_flash_window_fill``, set from the static shapes when a step with
window layers is traced (``ops/pallas_attention.py`` ``band_tiles``:
the kernels' own rule for which tiles run).  What is left to 100 is the
two masked edges of the band, the diagonal and the left one: scores
computed and thrown away.  Read for a traced run, as every per-layer
metric is; 0 where the gauge was never set (no window step traced).
None where the program declares no such gauge (a commit before it)."""

LAYER = "Sharded step"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "samples_per_s_chip"

GAUGE = "hvd_flash_window_fill"


def read(run: dict):
    if not run.get("trace"):
        return None
    from horovod_tpu import training  # noqa: F401  declares the steps' gauges
    from horovod_tpu.common import metrics
    if "# TYPE %s gauge\n" % GAUGE not in metrics.REGISTRY.render_prometheus():
        return None
    return 100.0 * metrics.gauge(GAUGE).value()
