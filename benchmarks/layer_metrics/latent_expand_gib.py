"""GiB one layer's forward pass writes on one device for the keys and
values that latent attention expands for every head, the one rotated
key repeated among them (what a kernel that read the latent, or the one
rotated key, would not write): the program's own gauge
``hvd_mla_expand_bytes``, set when its step is traced (a gauge never set
reads 0, the program's own rule: no such step was traced).  Read for a
traced run, as every per-layer metric is.  None where the program
declares no such gauge (a commit before it)."""

LAYER = "Sharded step"
UNIT = "GiB"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "samples_per_s_chip"

GAUGE = "hvd_mla_expand_bytes"


def read(run: dict):
    if not run.get("trace"):
        return None
    from horovod_tpu import training  # noqa: F401  declares the steps' gauges
    from horovod_tpu.common import metrics
    declared = "# TYPE %s gauge\n" % GAUGE
    if declared not in metrics.REGISTRY.render_prometheus():
        return None
    return metrics.gauge(GAUGE).value() / 2.0 ** 30
