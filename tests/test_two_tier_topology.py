"""Two-tier (DCN x ICI) topology rehearsal on localhost.

VERDICT r3 item 6: simulate 2 "hosts" x 2 "chips" through the env
contract (distinct HOROVOD_LOCAL_RANK/CROSS_RANK per rank), and prove
the hierarchical allreduce really splits local-RS -> cross-AR ->
local-AG on the right tiers — the test FAILS if the cross leg is
silently flat (numeric check), on the wrong tier (jaxpr axis check),
or if the hierarchical path wasn't taken at all (stats check).
Reference: ops/nccl_operations.cc:188-360 NCCLHierarchicalAllreduce
(NCCL reduce-scatter intra-node -> MPI allreduce cross-node -> NCCL
allgather).
"""

import pytest

from multiproc import assert_all_ok, run_workers

NPROC = 4
LOCAL = 2  # chips per simulated host


def two_tier_env(rank):
    return {
        "HOROVOD_LOCAL_RANK": rank % LOCAL,
        "HOROVOD_LOCAL_SIZE": LOCAL,
        "HOROVOD_CROSS_RANK": rank // LOCAL,
        "HOROVOD_CROSS_SIZE": NPROC // LOCAL,
    }


_HIER_BODY = """
import horovod_tpu as hvd
hvd.init()
from horovod_tpu.common import basics
be = basics._state().backend
assert type(be).__name__ == "XlaMeshBackend", type(be)

# The env contract produced the two-tier process mesh.
assert be._hier is not None and be._hier_kind == "proc", \
    (be._hier_kind, be._hier)
assert be._hier_nlocal == 2
grid = be._hier.devices
assert grid.shape == (2, 2)
# Rows = cross index = simulated host; each row's devices must belong
# to the two ranks of ONE host, each column spans both hosts.
for c in range(2):
    row_procs = sorted(d.process_index for d in grid[c])
    assert row_procs == [2 * c, 2 * c + 1], (c, row_procs)

# Numeric: result must be the GLOBAL sum — if the cross-AR leg were
# dropped (a silently flat hierarchy), each host would only see its
# local pair's sum and this fails.
x = np.arange(6, dtype=np.float32) + 100.0 * RANK
out = np.asarray(hvd.allreduce(x, op=hvd.Sum, name="tt.ar"))
exp = sum(np.arange(6, dtype=np.float32) + 100.0 * r
          for r in range(SIZE))
np.testing.assert_allclose(out, exp)

# The hierarchical path was actually taken (not the flat fallback).
assert be.stats.get("hierarchical_allreduces", 0) >= 1, be.stats
assert be.stats.get("flat_allreduces", 0) == 0, be.stats

# Tier structure: trace the PRODUCT hierarchical program and assert
# the op sequence and the axis each leg runs on — reduce-scatter over
# 'local', allreduce over 'cross', allgather over 'local'.
import jax, re
fn = type(be)._hier_proc_fn(be._hier, ((6,),), "Sum", 1.0, 1.0, SIZE)
from jax.sharding import NamedSharding, PartitionSpec as P
spec = jax.ShapeDtypeStruct(
    (2, 2, 6), np.float32,
    sharding=NamedSharding(be._hier, P("cross", "local")))
jaxpr = str(jax.make_jaxpr(fn)(spec))
rs = re.search(r"reduce_scatter\\[[^]]*axis_name=\\('(\\w+)',\\)",
               jaxpr)
ar = re.search(r"\\bpsum\\[[^]]*axes=\\('(\\w+)',\\)", jaxpr)
ag = re.search(r"all_gather\\[[^]]*axis_name=\\('(\\w+)',\\)", jaxpr)
assert rs and ar and ag, jaxpr
assert rs.group(1) == "local", jaxpr
assert ar.group(1) == "cross", jaxpr
assert ag.group(1) == "local", jaxpr
assert rs.start() < ar.start() < ag.start(), \
    (rs.start(), ar.start(), ag.start())
print("TWO-TIER-OK")
"""


def test_hierarchical_allreduce_two_tier():
    results = run_workers(
        _HIER_BODY, nproc=NPROC, timeout=240,
        extra_env={"HOROVOD_CPU_OPERATIONS": "XLA",
                   "HOROVOD_HIERARCHICAL_ALLREDUCE": "1"},
        per_rank_env=two_tier_env)
    assert_all_ok(results)
    assert all("TWO-TIER-OK" in out for _, out in results)


def test_hier_proc_per_rank_transfer_is_size_over_nlocal():
    """VERDICT r4 item 7: the compiled hierarchical program's byte
    movement must be TRUE RS->AR->AG — per-rank cross-tier (DCN)
    transfer exactly size/nlocal, not the full buffer.  Asserted at
    the HLO level: reduce-scatter emits L/nlocal per rank, the cross
    all-reduce operates on L/nlocal, and the local all-gather rebuilds
    L.  (The eager staging necessarily places each rank's own full
    input copy — that is the allreduce input, not replication.)"""
    import re

    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from horovod_tpu.ops.xla_ops import XlaMeshBackend

    ncross, nlocal, L = 2, 4, 1024
    devs = np.array(jax.devices()[:ncross * nlocal]).reshape(
        ncross, nlocal)
    mesh = Mesh(devs, ("cross", "local"))
    fn = XlaMeshBackend._hier_proc_fn(
        mesh, ((L,),), "Sum", 1.0, 1.0, ncross * nlocal)
    spec = jax.ShapeDtypeStruct(
        (ncross, nlocal, L), np.float32,
        sharding=NamedSharding(mesh, P("cross", "local")))
    hlo = fn.lower(spec).compile().as_text()

    rs = re.search(r"= f32\[(\d+)\]\{0\} reduce-scatter\(", hlo)
    ar = re.search(r"= f32\[(\d+)\]\{0\} all-reduce\(", hlo)
    ag = re.search(r"= f32\[(\d+)\]\{0\} all-gather\(", hlo)
    assert rs and ar and ag, hlo
    assert int(rs.group(1)) == L // nlocal, rs.group(0)   # local RS out
    assert int(ar.group(1)) == L // nlocal, ar.group(0)   # cross AR
    assert int(ag.group(1)) == L, ag.group(0)             # local AG out

    # Replica groups: RS/AG group whole rows (local tier), AR pairs
    # same-column devices across rows (cross tier).
    rs_line = hlo[rs.start():hlo.index("\n", rs.start())]
    ar_line = hlo[ar.start():hlo.index("\n", ar.start())]
    assert "{0,1,2,3}" in rs_line and "{4,5,6,7}" in rs_line, rs_line
    assert "{0,4}" in ar_line and "{3,7}" in ar_line, ar_line


def test_world_mesh_follows_ranks_not_process_indices():
    """A backend may number its processes another way than the
    launcher's ranks: on a v5e host with one chip a process, ranks
    0..3 came up as jax processes 3, 2, 0, 1.  Rehearsed here by
    joining jax.distributed under exactly that numbering before
    ``hvd.init()``: every rank-indexed collective must still see rank
    order."""
    results = run_workers("""
        from horovod_tpu.common import basics
        be = basics._state().backend
        assert type(be).__name__ == "XlaMeshBackend", type(be)
        assert jax.process_index() == PROCESS_OF_RANK[RANK]
        assert [d.process_index for d in be._reps] == PROCESS_OF_RANK

        g = np.asarray(hvd.allgather(
            np.full((RANK + 1, 2), float(RANK), np.float32), name="ro.ag"))
        np.testing.assert_array_equal(
            g[:, 0], np.repeat(np.arange(SIZE), np.arange(SIZE) + 1))
        b = np.asarray(hvd.broadcast(np.full(3, float(RANK), np.float32),
                                     root_rank=1, name="ro.bc"))
        np.testing.assert_array_equal(b, 1.0)
        y, recv = hvd.alltoall(
            np.full(SIZE, float(RANK), np.float32),
            splits=np.ones(SIZE, np.int64), name="ro.a2a")
        np.testing.assert_array_equal(np.asarray(y), np.arange(SIZE))
        rs = np.asarray(hvd.reducescatter(
            np.arange(SIZE, dtype=np.float32) + 10.0 * RANK, op=hvd.Sum,
            name="ro.rs"))
        np.testing.assert_array_equal(
            rs, [RANK * SIZE + 10.0 * sum(range(SIZE))])
        print("ORDER OK", RANK)
    """, nproc=NPROC, extra_env={"HOROVOD_CPU_OPERATIONS": "XLA"},
        before_init="""
        PROCESS_OF_RANK = [3, 2, 0, 1]
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(
            coordinator_address=os.environ["HOROVOD_TPU_COORDINATOR"],
            num_processes=int(os.environ["HOROVOD_SIZE"]),
            process_id=PROCESS_OF_RANK[int(os.environ["HOROVOD_RANK"])])
    """)
    assert_all_ok(results)
