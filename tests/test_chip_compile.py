"""The flash-attention forward compiles for the chip, asked without one.

The TPU's compiler is installed beside JAX and compiles for a v5e that
is described, not attached (the ``on-chip-measurement`` guide, section
2).  Interpret mode cannot show what these cases show: a tiling the
compiler refuses, too much fast memory, a kernel that is not there.
Nothing runs, so results and times are the chip's to give
(``chip_smoke.py``).
"""

import functools
import os

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

from horovod_tpu.ops.pallas_attention import flash_attention


@pytest.fixture(scope="module")
def v5e_chip():
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here: nothing to ask
        pytest.skip("cannot describe a v5e topology: %r" % (e,))
    return SingleDeviceSharding(topo.devices[0])


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
