"""Adasum reduction: scale-insensitive gradient combining.

Mirrors the reference Adasum algorithm (reference: ops/adasum/adasum.h:
38-547 — recursive vector-halving distance-doubling where each pairwise
merge is

    a' = (1 - a.b / (2‖a‖²)) a + (1 - a.b / (2‖b‖²)) b

with per-tensor dot products/norms computed over the *full* tensors at
every level (FusedAllreduce :194-336, coefficients :385-392), fp64
accumulation for fp16 inputs (:400-414), power-of-2 world sizes).

TPU mapping: recursive doubling over `lax.ppermute` pairs (i ↔ i^2^k).
The reference's vector-halving is a bandwidth optimization of the same
mathematics (halves travel, dots are allreduced); on ICI the ppermute
ladder is already contention-free, and XLA fuses the dot products into
the exchange program.  The pairwise formula is symmetric under operand
swap, so both partners compute the identical merged vector and after
log2(n) levels every member holds the Adasum result.

The hierarchical variant matches AdasumGpuAllreduceOp semantics
(reference: ops/adasum_gpu_operations.cc — intra-node sum via
ReduceScatter/Allgather, Adasum across nodes, with a 1/local_size
postscale applied by the enqueue layer, operations.cc:949-956).
"""

import math
from functools import lru_cache
from typing import List

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P



def _is_pow2(n: int) -> bool:
    return n > 0 and (n & (n - 1)) == 0


def adasum_pair_numpy(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Reference pairwise merge in numpy (test oracle; mirrors the
    Python reimplementation used by the reference's own
    test_adasum_pytorch.py)."""
    a64 = a.astype(np.float64).ravel()
    b64 = b.astype(np.float64).ravel()
    dot = float(a64 @ b64)
    na = float(a64 @ a64)
    nb = float(b64 @ b64)
    ca = 1.0 - dot / (2.0 * na) if na != 0.0 else 1.0
    cb = 1.0 - dot / (2.0 * nb) if nb != 0.0 else 1.0
    return (ca * a.astype(np.float64) +
            cb * b.astype(np.float64)).astype(a.dtype)


def adasum_reference_numpy(tensors: List[np.ndarray]) -> np.ndarray:
    """Tree-reduce a list of per-rank tensors with the Adasum rule
    (recursive doubling order: level k merges i with i^2^k)."""
    n = len(tensors)
    assert _is_pow2(n), "Adasum requires a power-of-2 member count"
    vals = [t.copy() for t in tensors]
    # Recursive doubling in list form: level k merges adjacent groups,
    # so repeatedly merging neighbors reproduces the i ↔ i^2^k ladder.
    while len(vals) > 1:
        vals = [adasum_pair_numpy(vals[i], vals[i + 1])
                for i in range(0, len(vals), 2)]
    return vals[0]


def adasum_reduce_ingraph(x: jax.Array, axis_name: str, n: int,
                          eps: float = 0.0) -> jax.Array:
    """Adasum over a mesh axis, callable inside shard_map/pjit.

    Dot products accumulate in float64 when inputs are half-precision
    (float32 otherwise is already exact enough and much faster on MXU).
    """
    if not _is_pow2(n):
        raise ValueError(
            f"Adasum requires a power-of-2 world size, got {n} "
            "(matching the reference implementation's constraint).")
    orig_dtype = x.dtype
    acc_dtype = jnp.float64 if x.dtype in (jnp.float16, jnp.bfloat16) \
        else jnp.float32
    v = x.astype(jnp.float32)
    for k in range(int(math.log2(n))):
        d = 1 << k
        perm = [(i, i ^ d) for i in range(n)]
        u = lax.ppermute(v, axis_name, perm)
        va = v.astype(acc_dtype).ravel()
        ua = u.astype(acc_dtype).ravel()
        dot = jnp.dot(va, ua)
        nv = jnp.dot(va, va)
        nu = jnp.dot(ua, ua)
        cv = jnp.where(nv != 0, 1.0 - dot / (2.0 * nv + eps), 1.0)
        cu = jnp.where(nu != 0, 1.0 - dot / (2.0 * nu + eps), 1.0)
        v = (cv.astype(jnp.float32) * v + cu.astype(jnp.float32) * u)
    return v.astype(orig_dtype)


def adasum_hierarchical_ingraph(x: jax.Array, local_axis: str,
                                cross_axis: str, n_cross: int) -> jax.Array:
    """Hierarchical Adasum: mean over the ICI-local axis, Adasum across
    the DCN axis (reference AdasumGpuAllreduceOp: NCCL ReduceScatter →
    Adasum-MPI VHDD → NCCL Allgather with 1/local_size postscale)."""
    local = lax.pmean(x, local_axis)
    return adasum_reduce_ingraph(local, cross_axis, n_cross)


@lru_cache(maxsize=256)
def _adasum_global_fn(mesh, n_tensors: int, size: int, prescale: float,
                      postscale: float):
    def body(*xs):
        out = []
        for x in xs:
            x = x[0]
            if prescale != 1.0:
                x = x * jnp.asarray(prescale, x.dtype)
            y = adasum_reduce_ingraph(x, "world", size)
            if postscale != 1.0:
                y = y * jnp.asarray(postscale, y.dtype)
            out.append(y)
        return tuple(out)

    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=tuple(P("world") for _ in range(n_tensors)),
        out_specs=tuple(P() for _ in range(n_tensors)), check_vma=False))


def adasum_allreduce_global(mesh, rep_device, size: int, arrays,
                            prescale: float, postscale: float):
    """Eager fused Adasum over the world mesh (multi-process path)."""
    shard_sharding = NamedSharding(mesh, P("world"))
    globals_, meta = [], []
    for x in arrays:
        was_jax = isinstance(x, jax.Array)
        arr = np.asarray(x) if not was_jax else x
        local = jax.device_put(jnp.asarray(arr)[None], rep_device)
        g = jax.make_array_from_single_device_arrays(
            (size,) + tuple(arr.shape), shard_sharding, [local])
        globals_.append(g)
        meta.append(was_jax)
    fn = _adasum_global_fn(mesh, len(globals_), size, float(prescale),
                           float(postscale))
    outs = fn(*globals_)
    results = []
    for o, was_jax in zip(outs, meta):
        local = o.addressable_data(0)
        results.append(local if was_jax else np.asarray(local))
    return results
