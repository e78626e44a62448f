"""Device-mesh construction and multi-host initialization.

The reference builds its process mesh by parsing a machine-list file and
pairwise-connecting TCP sockets (``Linkers::Construct``,
``src/network/linkers_socket.cpp``) or from ``MPI_COMM_WORLD``
(``linkers_mpi.cpp``).  Here the runtime owns topology: we only name axes on
`jax.sharding.Mesh` and let XLA route collectives over ICI/DCN.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np

DATA_AXIS = "data"
FEATURE_AXIS = "feature"


def default_mesh(num_devices: Optional[int] = None,
                 axis_name: str = DATA_AXIS,
                 devices: Optional[Sequence] = None) -> jax.sharding.Mesh:
    """1-D mesh over (a prefix of) the available devices."""
    if devices is None:
        devices = jax.devices()
    if num_devices is not None:
        if num_devices > len(devices):
            raise ValueError(
                f"requested {num_devices} devices, only {len(devices)} available")
        devices = devices[:num_devices]
    return jax.sharding.Mesh(np.asarray(devices), (axis_name,))


def mesh_2d(num_data: int, num_feature: int,
            devices: Optional[Sequence] = None) -> jax.sharding.Mesh:
    """(data, feature) mesh for combined row+feature sharding."""
    if devices is None:
        devices = jax.devices()
    n = num_data * num_feature
    if n > len(devices):
        raise ValueError(f"mesh {num_data}x{num_feature} needs {n} devices, "
                         f"only {len(devices)} available")
    arr = np.asarray(devices[:n]).reshape(num_data, num_feature)
    return jax.sharding.Mesh(arr, (DATA_AXIS, FEATURE_AXIS))


def init_distributed(coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None,
                     timeout_secs: Optional[int] = None) -> None:
    """Multi-host bring-up (replaces ``LGBM_NetworkInit`` + machine lists,
    ``c_api.cpp`` / ``application.cpp:167-202``).  On TPU pods all arguments
    are discovered from the environment."""
    kw = {}
    if timeout_secs is not None:
        kw["initialization_timeout"] = int(timeout_secs)
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id, **kw)


def set_network(machines, local_listen_port: int = 12400,
                listen_time_out: int = 120,
                num_machines: Optional[int] = None) -> None:
    """Reference ``Booster.set_network`` analog: bring up the
    ``jax.distributed`` client from a machine list.

    ``machines`` is a list/set or a comma-separated string of
    ``host[:port]`` entries — the FIRST entry becomes the coordinator
    (the reference's rank-0 socket hub).  This process's rank is the
    index of its entry, resolved by matching a local interface address
    or hostname; pass ``host:port`` entries whose hosts are resolvable.
    ``listen_time_out`` maps to the coordinator connect timeout.
    """
    import socket

    if isinstance(machines, str):
        entries = [m.strip() for m in machines.split(",") if m.strip()]
    else:
        entries = [str(m).strip() for m in machines]
        if isinstance(machines, (set, frozenset)):
            # per-process hash randomization would make each rank see a
            # different entry order (different coordinator!) — sort for a
            # deterministic shared view
            entries = sorted(entries)
    if num_machines is None:
        num_machines = len(entries)
    hosts = [e.split(":")[0] for e in entries]
    coord_host = hosts[0]
    coord_port = (int(entries[0].split(":")[1]) if ":" in entries[0]
                  else local_listen_port)

    local_names = {socket.gethostname(), "localhost", "127.0.0.1"}
    try:
        local_names.add(socket.gethostbyname(socket.gethostname()))
    except OSError:
        pass

    def _is_local_addr(addr: str) -> bool:
        """A bind() to addr succeeds exactly when addr belongs to a local
        interface — robust where hostname mapping is not (e.g. Debian's
        127.0.1.1 /etc/hosts entry hides the real NIC address)."""
        try:
            with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as s:
                s.bind((addr, 0))
            return True
        except OSError:
            return False

    addrs = []
    for h in hosts:
        try:
            addrs.append(socket.gethostbyname(h))
        except OSError:
            addrs.append(h)
    matches = [i for i, (h, a) in enumerate(zip(hosts, addrs))
               if h in local_names or a in local_names]
    if not matches:
        # fallback for hosts whose hostname does not map to the NIC
        # address (Debian's 127.0.1.1 /etc/hosts entry): bind-probe each
        # entry.  Only as a fallback — the whole 127/8 block is bindable,
        # so loopback multi-entry lists must resolve by name above.
        matches = [i for i, a in enumerate(addrs) if _is_local_addr(a)]
    if len(matches) > 1:
        # same host listed multiple times (multi-process-per-box layout):
        # hostname matching cannot tell the processes apart
        raise ValueError(
            f"set_network: machine entries {[entries[i] for i in matches]} "
            "all resolve to this host; assign ranks explicitly with "
            "init_distributed(coordinator_address, num_processes, "
            "process_id)")
    rank = matches[0] if matches else None
    if rank is None:
        raise ValueError(
            f"set_network: none of the machine entries {hosts} resolves to "
            "this host; use init_distributed(coordinator_address, "
            "num_processes, process_id) to assign the rank explicitly")
    init_distributed(coordinator_address=f"{coord_host}:{coord_port}",
                     num_processes=num_machines, process_id=rank,
                     timeout_secs=int(listen_time_out) * 60)  # ref: minutes


def free_network() -> None:
    """Reference ``LGBM_NetworkFree`` analog."""
    jax.distributed.shutdown()
