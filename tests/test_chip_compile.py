"""The flash-attention forward and BERT's dropout step compile for the
chip, asked without one.

The TPU's compiler is installed beside JAX and compiles for a v5e that
is described, not attached (the ``on-chip-measurement`` guide, section
2).  Interpret mode cannot show what these cases show: a tiling the
compiler refuses, too much fast memory, a kernel that is not there.
Nothing runs, so results and times are the chip's to give
(``chip_smoke.py``).
"""

import functools
import os
import re
from collections import Counter

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")
# Nothing here opens a device, so another process that has libtpu
# loaded (a rehearsal compile, another checkout's tests) is no reason
# to be refused the library.
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")

import jax
import jax.numpy as jnp
from jax.experimental import topologies
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from horovod_tpu.models.bert import bert_tiny_config
from horovod_tpu.ops.pallas_attention import flash_attention
from horovod_tpu.parallel.mesh import build_mesh
from horovod_tpu.parallel.sharding import (bert_partition_rules,
                                           infer_shardings)
from horovod_tpu.training import make_bert_pretrain_step


@pytest.fixture(scope="module")
def v5e_2x2():
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here: nothing to ask
        pytest.skip("cannot describe a v5e topology: %r" % (e,))


@pytest.fixture(scope="module")
def v5e_chip(v5e_2x2):
    return SingleDeviceSharding(v5e_2x2.devices[0])


@pytest.fixture(autouse=True)
def no_compilation_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without the chip; the next one would
    warn and compile again."""
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("shape,dtype,causal", [
    ((8, 128, 16, 64), jnp.bfloat16, False),    # BERT-large heads, S=128
    ((8, 512, 16, 64), jnp.bfloat16, False),    # BERT-large heads, S=512
    ((4, 1024, 12, 64), jnp.bfloat16, True),    # GPT-2 heads, causal
    ((2, 512, 16, 128), jnp.bfloat16, True),    # head dim 128, causal
    ((2, 500, 16, 64), jnp.float32, False),     # ragged length, float32
], ids=["bert-s128", "bert-s512", "gpt2-s1024-causal", "d128-causal",
        "ragged-s500-f32"])
def test_flash_forward_compiles_for_v5e(v5e_chip, shape, dtype, causal):
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)
    compiled = jax.jit(functools.partial(
        flash_attention, causal=causal)).lower(x, x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("chips", [1, 4])
def test_bert_dropout_bits_are_made_shard_by_shard(v5e_2x2, chips):
    """The TPU compiler's own text of the tiny BERT step with dropout:
    one ``rng-bit-generator`` a site, and under ``dp=4`` each of the
    shard's shape (a quarter of the batch), not the global one that
    XLA's partitioner makes on every chip when left to its default."""
    per_chip, seq = 8, 128
    cfg = bert_tiny_config(hidden_dropout=0.1, attention_dropout=0.1)
    mesh = build_mesh({"dp": chips}, v5e_2x2.devices[:chips])
    make_jitted, batch_sharding = make_bert_pretrain_step(cfg, mesh)
    batch = {name: jax.ShapeDtypeStruct((per_chip * chips, seq), jnp.int32,
                                        sharding=batch_sharding)
             for name in ("input_ids", "labels", "mask")}
    init_fn, step_fn = make_jitted(batch)
    state = jax.eval_shape(
        init_fn, jax.ShapeDtypeStruct((2,), jnp.uint32), batch)
    state = jax.tree.map(
        lambda leaf, sharding: jax.ShapeDtypeStruct(
            leaf.shape, leaf.dtype, sharding=sharding),
        state, infer_shardings(state, mesh, bert_partition_rules(tp=None)))
    text = step_fn.lower(state, batch).compile().as_text()

    made = Counter(re.findall(
        r"= u32\[([0-9,]+)\]\S* rng-bit-generator\(", text))
    assert made == {
        "%d,%d,%d" % (per_chip, seq, cfg.hidden_size):
            2 * cfg.num_layers + 1,
        "%d,%d,%d,%d" % (per_chip, cfg.num_heads, seq, seq):
            cfg.num_layers}, made
