"""Multi-host runtime: one logical device mesh spanning OS processes.

reference: the MPI plane — ``mpirun`` launches N ranks, each rank binds a GPU,
and NCCL/MPI collectives move tensors between them
(``simulation/mpi/base_framework/``, ``core/distributed/communication/mpi/
mpi_comm_manager.py``, gRPC/TRPC variants). That is the reference's only way
to scale past one process.

TPU re-grounding: JAX's runtime already *is* the multi-process backend — each
host in a pod runs one process, ``jax.distributed.initialize`` connects them
through a coordinator, and afterwards ``jax.devices()`` is the GLOBAL device
list, so the same ``Mesh`` + ``pjit`` program runs unchanged with XLA moving
data over ICI/DCN. No per-message send/recv code exists at all — the mesh
APIs (``mesh_api``, ``train_step``, ``pipeline``) become multi-host by
construction. This module supplies the two missing pieces:

- ``initialize(...)`` — rank bootstrap (the analog of ``MPI.Init`` +
  NCCL communicator setup), driven by env vars that cover TPU pods
  (``megascale`` auto-detection), GCE, SLURM, and the explicit
  coordinator/rank form the launcher uses;
- ``spawn(worker_argv, n_processes, ...)`` — a single-machine N-process
  launcher (the analog of ``mpirun -np N``) used by tests and by
  ``examples/``: every child gets the coordinator address, its process id,
  and a ``--xla_force_host_platform_device_count`` fan-out so multi-host
  semantics (device locality, cross-process collectives over the gRPC
  coordinator) are exercised for real without N machines.

The launcher is a CPU EMULATION, and only that: ``spawn`` always passes a
``local_device_count`` (``fedml_tpu multihost`` defaults it to 1), and
``initialize`` with a ``local_device_count`` forces the ``cpu`` platform, so
no spawned worker ever opens a chip. A 2-process × 4 virtual-device run has
the same global/local device split, the same addressable-shard semantics and
the same collective routing as a 2-host pod slice — only the wire underneath
differs. On real hardware one process drives all chips of a host; a pod runs
one process per host, each calling ``initialize()`` with no arguments.
"""

from __future__ import annotations

import logging
import os
import socket
import subprocess
import sys
from typing import Dict, List, Optional, Sequence

logger = logging.getLogger("fedml_tpu.multihost")

ENV_COORDINATOR = "FEDML_TPU_COORDINATOR"
ENV_PROCESS_ID = "FEDML_TPU_PROCESS_ID"
ENV_NUM_PROCESSES = "FEDML_TPU_NUM_PROCESSES"
ENV_LOCAL_DEVICES = "FEDML_TPU_LOCAL_DEVICES"


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def initialize(coordinator: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None,
               local_device_count: Optional[int] = None) -> None:
    """Join this process to the global runtime (analog of MPI.Init).

    Resolution order mirrors how pods are actually launched: explicit args,
    then the ``FEDML_TPU_*`` env contract set by :func:`spawn`, then JAX's
    own auto-detection (TPU pod metadata / SLURM), which needs no args at
    all. Must run before first jax backend touch.
    """
    coordinator = coordinator or os.environ.get(ENV_COORDINATOR)
    if num_processes is None and ENV_NUM_PROCESSES in os.environ:
        num_processes = int(os.environ[ENV_NUM_PROCESSES])
    if process_id is None and ENV_PROCESS_ID in os.environ:
        process_id = int(os.environ[ENV_PROCESS_ID])
    if local_device_count is None and ENV_LOCAL_DEVICES in os.environ:
        local_device_count = int(os.environ[ENV_LOCAL_DEVICES])

    if local_device_count:  # virtual CPU fan-out for emulation runs
        import re

        flags = os.environ.get("XLA_FLAGS", "")
        opt = "xla_force_host_platform_device_count"
        if re.search(rf"{opt}=\d+", flags):  # override an inherited fan-out
            flags = re.sub(rf"{opt}=\d+", f"{opt}={local_device_count}", flags)
        else:
            flags = (flags + f" --{opt}={local_device_count}").strip()
        os.environ["XLA_FLAGS"] = flags

    import jax

    if local_device_count:
        jax.config.update("jax_platforms", "cpu")
    if coordinator is None and num_processes is None:
        # TPU pod / SLURM: jax works out everything from the environment
        jax.distributed.initialize()
    else:
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=num_processes,
            process_id=process_id,
        )
    logger.info(
        "multihost: process %d/%d up, %d local / %d global devices",
        jax.process_index(), jax.process_count(),
        jax.local_device_count(), jax.device_count(),
    )


def spawn(worker_argv: Sequence[str], n_processes: int,
          local_device_count: int = 1,
          coordinator_port: Optional[int] = None,
          env: Optional[Dict[str, str]] = None,
          timeout_s: float = 300.0) -> List[subprocess.CompletedProcess]:
    """Run ``worker_argv`` as N coordinated CPU-emulation processes (analog:
    mpirun -np N); each worker gets ``local_device_count`` virtual CPU
    devices and never a chip.

    Children read the ``FEDML_TPU_*`` env contract and call
    :func:`initialize` (no args) before touching jax. Returns the completed
    processes; raises if any exits nonzero, with its tail echoed.
    """
    import threading
    import time

    port = coordinator_port or free_port()
    procs = []
    for pid in range(n_processes):
        child_env = dict(os.environ)
        child_env.update(env or {})
        child_env.update({
            ENV_COORDINATOR: f"127.0.0.1:{port}",
            ENV_PROCESS_ID: str(pid),
            ENV_NUM_PROCESSES: str(n_processes),
            ENV_LOCAL_DEVICES: str(local_device_count),
        })
        procs.append(subprocess.Popen(
            [sys.executable, *worker_argv], env=child_env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        ))

    # drain every pipe concurrently: ranks block on collectives together, so
    # one undrained worker filling its pipe buffer would deadlock the mesh
    outputs: List[Optional[str]] = [None] * n_processes

    def _drain(idx: int, p: subprocess.Popen) -> None:
        out, _ = p.communicate()
        outputs[idx] = out

    drainers = [threading.Thread(target=_drain, args=(i, p), daemon=True)
                for i, p in enumerate(procs)]
    for t in drainers:
        t.start()
    # one shared deadline: n sequential joins must not stretch the documented
    # timeout to n * timeout_s
    deadline = time.monotonic() + timeout_s
    for t in drainers:
        t.join(timeout=max(0.0, deadline - time.monotonic()))
    if any(t.is_alive() for t in drainers):
        for q in procs:
            q.kill()
        for t in drainers:
            t.join(timeout=10)  # collect post-kill output for the error
        tails = "\n".join(
            f"--- worker {i} tail ---\n" +
            "\n".join((outputs[i] or "").splitlines()[-10:])
            for i in range(n_processes)
        )
        raise TimeoutError(
            f"multihost launch exceeded {timeout_s}s; workers killed.\n{tails}"
        )

    done = [
        subprocess.CompletedProcess(p.args, p.returncode, outputs[i] or "")
        for i, p in enumerate(procs)
    ]
    for pid, r in enumerate(done):
        if r.returncode != 0:
            tail = "\n".join(r.stdout.splitlines()[-25:])
            raise RuntimeError(
                f"multihost worker {pid} exited nonzero:\n{tail}"
            )
    return done
