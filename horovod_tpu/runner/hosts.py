"""Host parsing and slot planning for the launcher.

The TPU analog of the reference's host/slot math (reference:
runner/common/util/hosts.py:34-156 — ``SlotInfo``, ``parse_hosts``,
``get_host_assignments``): a *slot* is one launched worker process.  On
TPU pods a slot is normally one TPU-VM host (each process then owns its
``jax.local_devices()`` chips and in-graph mesh parallelism covers the
chips), but ``--slots-per-host`` can split a host into per-chip slots
like the reference's per-GPU processes.

Rank-ordering contract (identical to the reference): ranks are assigned
host-major in the order hosts are listed, so consecutive ranks land on
the same host and hierarchical (ICI-then-DCN) collectives see contiguous
local groups.  ``cross_rank`` indexes a slot's host among all hosts that
have a slot at the same ``local_rank``.
"""

import collections
import dataclasses
import re
from typing import Dict, List, Optional, Sequence, Tuple


class HostInfo:
    """One entry of a ``host:slots`` list."""

    def __init__(self, hostname: str, slots: int):
        self.hostname = hostname
        self.slots = slots

    @staticmethod
    def from_string(host_string: str) -> "HostInfo":
        hostname, slots = host_string.strip().split(":")
        return HostInfo(hostname, int(slots))

    def __repr__(self):
        return f"HostInfo({self.hostname}:{self.slots})"

    def __eq__(self, other):
        return (isinstance(other, HostInfo)
                and self.hostname == other.hostname
                and self.slots == other.slots)


@dataclasses.dataclass
class SlotInfo:
    """Full rank identity of one worker slot."""
    hostname: str
    rank: int
    local_rank: int
    cross_rank: int
    size: int
    local_size: int
    cross_size: int

    def to_response_string(self) -> str:
        """Wire format served by the elastic rendezvous handler."""
        return ",".join(str(v) for v in (
            self.rank, self.size, self.local_rank, self.local_size,
            self.cross_rank, self.cross_size))


INVALID_SLOT_INFO = SlotInfo(hostname="", rank=-1, local_rank=-1,
                             cross_rank=-1, size=-1, local_size=-1,
                             cross_size=-1)

_HOST_PATTERN = re.compile(r"^[\w.\-]+:[0-9]+$")


def parse_hosts(hosts_string: str) -> List[HostInfo]:
    """``"h1:4,h2:4"`` → ``[HostInfo]``; validates every entry."""
    hosts = []
    for host_string in hosts_string.split(","):
        host_string = host_string.strip()
        if not _HOST_PATTERN.match(host_string):
            raise ValueError(
                "Invalid host input %r: expected format "
                "'worker-0:2,worker-1:2'." % host_string)
        hosts.append(HostInfo.from_string(host_string))
    return hosts


def parse_host_files(filename: str) -> str:
    """Read an MPI-style hostfile (``host slots=N``) into the
    comma-separated ``host:N`` form the CLI takes."""
    hosts = []
    with open(filename) as f:
        for line in f:
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            hostname = line.split()[0]
            slots = 1
            if "=" in line:
                slots = int(line.split("=")[1])
            hosts.append(f"{hostname}:{slots}")
    return ",".join(hosts)


def parse_hosts_and_slots(hosts: str) -> Tuple[List[str], Dict[str, int]]:
    infos = parse_hosts(hosts)
    return ([h.hostname for h in infos],
            {h.hostname: h.slots for h in infos})


def get_host_assignments(hosts: List[HostInfo], min_np: int,
                         max_np: Optional[int] = None) -> List[SlotInfo]:
    """Assign ranks to host slots, host-major.

    Packs as many consecutive ranks as possible onto each host (locality
    for the ICI leg of hierarchical collectives), stopping at ``max_np``
    total processes; raises if fewer than ``min_np`` slots exist.
    """
    cross_ranks: Dict[int, Dict[str, int]] = collections.defaultdict(dict)
    host_ranks: List[Tuple[HostInfo, List[int]]] = []
    rank = 0
    for host in hosts:
        ranks = []
        for local_rank in range(host.slots):
            if rank == max_np:
                break
            ranks.append(rank)
            rank += 1
            at_local = cross_ranks[local_rank]
            at_local[host.hostname] = len(at_local)
        host_ranks.append((host, ranks))

    world_size = rank
    if world_size < min_np:
        raise ValueError(
            "Requested more processes (%d) than there are available "
            "slots (%d)" % (min_np, world_size))

    alloc: List[SlotInfo] = []
    for host, ranks in host_ranks:
        local_size = len(ranks)
        for local_rank, rank in enumerate(ranks):
            at_local = cross_ranks[local_rank]
            alloc.append(SlotInfo(
                hostname=host.hostname,
                rank=rank,
                local_rank=local_rank,
                cross_rank=at_local[host.hostname],
                size=world_size,
                local_size=local_size,
                cross_size=len(at_local)))
    return alloc


def slot_env_vars(slot: SlotInfo) -> Dict[str, str]:
    """The launcher → worker rank contract (consumed by
    ``horovod_tpu.common.env.RankInfo.from_env``)."""
    return {
        "HOROVOD_HOSTNAME": slot.hostname,
        "HOROVOD_RANK": str(slot.rank),
        "HOROVOD_SIZE": str(slot.size),
        "HOROVOD_LOCAL_RANK": str(slot.local_rank),
        "HOROVOD_LOCAL_SIZE": str(slot.local_size),
        "HOROVOD_CROSS_RANK": str(slot.cross_rank),
        "HOROVOD_CROSS_SIZE": str(slot.cross_size),
    }


# The grid a host's processes form when each owns one TPU chip
# (TPU_PROCESS_BOUNDS), by the number of local slots.  Four processes on
# the 2x2 of a v5e host ran on the chip (PR 21).  Two processes on two
# of its four chips ("2,1,1", chips 0 and 1) both exited at start-up
# with code 1 and no message, so that count is refused, not guessed at.
_TPU_PROCESS_GRID = {4: "2,2,1"}


def tpu_chip_env(slot: SlotInfo, ports: Sequence[int],
                 tpu_host: bool = False) -> Dict[str, str]:
    """What the TPU runtime reads to bind one local slot to one chip
    of its host and to find its peers, for a job on a single host:
    which chip the process may open, the one-chip bounds of a process,
    the grid the processes form, every process's address, this
    process's port and its task id.  ``ports`` holds one free port per
    local slot, the same list for every slot.

    A host with one slot gets none of it: that process owns every chip
    it finds.  Several slots a host with no known layout (any count but
    four, or a job over several hosts) are refused on a ``tpu_host``,
    where the workers would otherwise all open every chip and hang; off
    the TPU the variables mean nothing and any layout runs."""
    if slot.local_size == 1:
        return {}
    grid = _TPU_PROCESS_GRID.get(slot.local_size) \
        if slot.cross_size == 1 else None
    if grid is None:
        if tpu_host:
            raise ValueError(
                "cannot give each of %d local slots on %d host(s) its "
                "own TPU chip; the layouts known to work are one slot "
                "a host (one process drives every chip) and four slots "
                "on a single 2x2 host"
                % (slot.local_size, slot.cross_size))
        return {}
    return {
        "TPU_VISIBLE_CHIPS": str(slot.local_rank),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": grid,
        "TPU_PROCESS_ADDRESSES": ",".join(
            "localhost:%d" % p for p in ports),
        "TPU_PROCESS_PORT": str(ports[slot.local_rank]),
        "CLOUD_TPU_TASK_ID": str(slot.local_rank),
        # Each process loads libtpu for itself; without this the
        # second one to start refuses, seeing the first one's lock.
        "ALLOW_MULTIPLE_LIBTPU_LOAD": "1",
    }
