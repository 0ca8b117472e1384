"""In-graph collective tests on the 8-device virtual CPU mesh.

Analog of the reference's per-op distributed correctness tests
(test/parallel/test_tensorflow.py ops × dtypes), but device-level: the
8-device mesh stands in for a TPU slice.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from horovod_tpu import parallel as par


def _shard_map(fn, mesh, in_specs, out_specs):
    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs))


def test_allreduce_sum(cpu_mesh8):
    mesh = cpu_mesh8
    x = jnp.arange(8.0).reshape(8, 1)
    f = _shard_map(lambda a: par.allreduce_sum(a, "dp"), mesh,
                   P("dp"), P("dp"))
    y = f(x)
    np.testing.assert_allclose(np.asarray(y), np.full((8, 1), 28.0))


def test_allreduce_mean(cpu_mesh8):
    mesh = cpu_mesh8
    x = jnp.arange(8.0).reshape(8, 1)
    f = _shard_map(lambda a: par.allreduce_mean(a, "dp"), mesh,
                   P("dp"), P("dp"))
    np.testing.assert_allclose(np.asarray(f(x)), np.full((8, 1), 3.5))


def test_allreduce_min_max(cpu_mesh8):
    mesh = cpu_mesh8
    x = jnp.arange(8.0).reshape(8, 1)
    fmin = _shard_map(lambda a: par.allreduce_min(a, "dp"), mesh,
                      P("dp"), P("dp"))
    fmax = _shard_map(lambda a: par.allreduce_max(a, "dp"), mesh,
                      P("dp"), P("dp"))
    np.testing.assert_allclose(np.asarray(fmin(x)), np.zeros((8, 1)))
    np.testing.assert_allclose(np.asarray(fmax(x)), np.full((8, 1), 7.0))


def test_allgather(cpu_mesh8):
    mesh = cpu_mesh8
    x = jnp.arange(16.0).reshape(8, 2)
    f = _shard_map(lambda a: par.allgather(a, "dp", axis=0), mesh,
                   P("dp"), P("dp"))
    y = f(x)
    # Each member gathers the full 8x2; replicated out over dp then
    # stacked back: global result is 64 rows of the tiled gather.
    assert y.shape == (64, 2)
    np.testing.assert_allclose(np.asarray(y)[:8], np.asarray(x))


def test_reduce_scatter(cpu_mesh8):
    mesh = cpu_mesh8
    # Every member contributes a full (8, 8); each receives its summed
    # (1, 8) shard.
    x = jnp.ones((8, 8))
    f = jax.jit(jax.shard_map(
        lambda a: par.reduce_scatter(a, "dp", axis=0), mesh=mesh,
        in_specs=P(None, None), out_specs=P("dp", None),
        check_vma=False))
    y = f(x)
    assert y.shape == (8, 8)
    np.testing.assert_allclose(np.asarray(y), np.full((8, 8), 8.0))


def test_broadcast(cpu_mesh8):
    mesh = cpu_mesh8
    x = jnp.arange(8.0).reshape(8, 1)
    f = _shard_map(lambda a: par.broadcast(a, root_rank=3,
                                           axis_name="dp"), mesh,
                   P("dp"), P("dp"))
    np.testing.assert_allclose(np.asarray(f(x)), np.full((8, 1), 3.0))


def test_alltoall(cpu_mesh8):
    mesh = cpu_mesh8
    # Each member holds 8 values destined one per member.
    x = jnp.arange(64.0).reshape(8, 8)
    f = _shard_map(lambda a: par.alltoall(a[0], "dp", split_axis=0,
                                          concat_axis=0)[None], mesh,
                   P("dp"), P("dp"))
    y = np.asarray(f(x))
    # Member i receives element i from every member: column i transposed.
    expect = np.arange(64.0).reshape(8, 8).T
    np.testing.assert_allclose(y, expect)


def test_ppermute_shift(cpu_mesh8):
    mesh = cpu_mesh8
    x = jnp.arange(8.0).reshape(8, 1)
    f = _shard_map(lambda a: par.neighbor_shift(a, 1, "dp"), mesh,
                   P("dp"), P("dp"))
    y = np.asarray(f(x)).ravel()
    np.testing.assert_allclose(y, np.roll(np.arange(8.0), 1))


def test_hierarchical_allreduce(cpu_mesh8):
    from horovod_tpu.parallel import build_mesh
    mesh = build_mesh({"cross": 2, "local": 4})
    x = jnp.arange(8.0).reshape(2, 4)
    f = jax.jit(jax.shard_map(
        lambda a: par.hierarchical_allreduce_sum(a, "local", "cross"),
        mesh=mesh, in_specs=P("cross", "local"),
        out_specs=P("cross", "local")))
    y = np.asarray(f(x))
    np.testing.assert_allclose(y, np.full((2, 4), 28.0))


def test_hierarchical_allreduce_uneven_padding(cpu_mesh8):
    # Element count not divisible by local axis size exercises padding.
    from horovod_tpu.parallel import build_mesh
    mesh = build_mesh({"cross": 2, "local": 4})
    def body(a):
        return par.hierarchical_allreduce_sum(a, "local", "cross")
    f = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=P(None, None), out_specs=P(None, None),
        check_vma=False))
    x = jnp.ones((3, 5))
    y = np.asarray(f(x))
    np.testing.assert_allclose(y, np.full((3, 5), 8.0))


def test_mesh_factory_default():
    from horovod_tpu.parallel import build_mesh
    mesh = build_mesh()
    assert mesh.shape["dp"] == 8


def test_mesh_factory_axes():
    from horovod_tpu.parallel import build_mesh
    mesh = build_mesh({"dp": -1, "tp": 2})
    assert mesh.shape["dp"] == 4 and mesh.shape["tp"] == 2


def test_parse_mesh_axes():
    from horovod_tpu.parallel import parse_mesh_axes
    assert parse_mesh_axes("dp:4,tp:2") == {"dp": 4, "tp": 2}
    assert parse_mesh_axes("dp") == {"dp": -1}
