"""GiB one layer's selection of keys writes on one device in its
forward pass, for the passes after it to read: the program's own gauge
``hvd_dsa_select_bytes``, set when its step is traced (the packed mask,
a bit a pair of query and key, and a float32 a query; the index scores
themselves live a block of queries at a time and are not among them).
A gauge never set reads 0, the program's own rule: no such step was
traced.  Read for a traced run, as every per-layer metric is.  None
where the program declares no such gauge (a commit before it)."""

LAYER = "Sharded step"
UNIT = "GiB"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "samples_per_s_chip"

GAUGE = "hvd_dsa_select_bytes"


def read(run: dict):
    if not run.get("trace"):
        return None
    from horovod_tpu import training  # noqa: F401  declares the steps' gauges
    from horovod_tpu.common import metrics
    declared = "# TYPE %s gauge\n" % GAUGE
    if declared not in metrics.REGISTRY.render_prometheus():
        return None
    return metrics.gauge(GAUGE).value() / 2.0 ** 30
