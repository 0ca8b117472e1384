"""One sharded training step of every parallel layout on the 8-device
virtual CPU mesh: the sharding rules compile, the step executes, the
loss is finite.  Whether a layout is fast is the chip's to say."""

import math

import pytest

import jax

from horovod_tpu import training


@pytest.mark.parametrize("dry_run,axes", [
    ("run_bert_dry_run", {"dp": 2, "tp": 2, "sp": 2}),
    ("run_pipeline_moe_dry_run", {"pp": 2, "ep": 2, "dp": 2}),
    ("run_ring_attention_dry_run", {"sp": 8}),
    ("run_gpt_dry_run", {"dp": 4, "tp": 2}),
    ("run_gpt_fsdp_dry_run", {"fsdp": 4, "tp": 2}),
])
def test_dry_run_on_eight_devices(dry_run, axes):
    assert jax.device_count() == 8
    result = getattr(training, dry_run)(8)
    loss, mesh = result if isinstance(result, tuple) else (0.0, result)
    assert math.isfinite(loss)
    assert dict(mesh.shape) == axes
