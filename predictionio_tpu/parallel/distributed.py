"""Multi-host initialization — the spark-submit boundary, TPU-style.

The reference reaches a cluster by shelling out to ``spark-submit``
(tools/Runner.scala:92-210) with ``PIO_*`` env forwarded. The TPU-native
equivalent (SURVEY.md §2.9, §5) is one Python process per TPU host, all
calling :func:`initialize` so XLA collectives span ICI within a slice and
DCN across slices. The CLI launcher invokes this before building a
:class:`~predictionio_tpu.parallel.mesh.ComputeContext`, which then sees
the global device set.

Env contract (mirrors the reference's env-var process boundary):

* ``PIO_COORDINATOR_ADDRESS`` — host:port of process 0
* ``PIO_NUM_PROCESSES`` / ``PIO_PROCESS_ID`` — world size / rank

On single-host runs (or TPU pods, where jax can infer everything from the
metadata server) all are optional.
"""

from __future__ import annotations

import logging
import os

import jax

logger = logging.getLogger(__name__)

_initialized = False


def initialize(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> None:
    """Join the multi-host job. No-op when single-process."""
    global _initialized
    if _initialized:
        return
    coordinator_address = coordinator_address or os.environ.get(
        "PIO_COORDINATOR_ADDRESS"
    )
    if num_processes is None and "PIO_NUM_PROCESSES" in os.environ:
        num_processes = int(os.environ["PIO_NUM_PROCESSES"])
    if process_id is None and "PIO_PROCESS_ID" in os.environ:
        process_id = int(os.environ["PIO_PROCESS_ID"])
    if coordinator_address is None and num_processes is None:
        # single process — nothing to coordinate
        return
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    _initialized = True
    logger.info(
        "jax.distributed initialized: process %d/%d, %d global devices",
        jax.process_index(),
        jax.process_count(),
        len(jax.devices()),
    )


def is_coordinator() -> bool:
    return jax.process_index() == 0


def _free_port() -> int:
    import socket

    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def launch_processes(
    argv: list[str],
    num_processes: int,
    coordinator_address: str | None = None,
    env: dict | None = None,
    timeout: float | None = None,
) -> int:
    """Spawn ``num_processes`` copies of ``argv`` with the multi-host env
    contract set — the ``spark-submit`` boundary
    (tools/Runner.scala:92-210: one driver process launched with PIO_*
    env forwarded; here one process per TPU host, rank in env).

    Each child gets ``PIO_COORDINATOR_ADDRESS`` / ``PIO_NUM_PROCESSES``
    / ``PIO_PROCESS_ID`` on top of the parent env (so ``PIO_STORAGE_*``
    flows through exactly as the reference forwards it). Returns the
    first nonzero child exit code, else 0; on failure or timeout the
    remaining children are terminated.

    One process drives every chip of its host, and a chip belongs to
    one process, so ``num_processes > 1`` is for several hosts (or the
    CPU backend's virtual devices): N copies started on ONE TPU host
    each try to take all of its chips, and all but the first fail at
    backend init. Nothing here pins a process to a subset of chips.
    """
    import subprocess
    import time as _time

    if num_processes < 1:
        raise ValueError("num_processes must be ≥ 1")
    coordinator_address = (
        coordinator_address or f"127.0.0.1:{_free_port()}"
    )
    base_env = dict(os.environ if env is None else env)
    base_env["PIO_COORDINATOR_ADDRESS"] = coordinator_address
    base_env["PIO_NUM_PROCESSES"] = str(num_processes)
    procs = []
    for rank in range(num_processes):
        child_env = dict(base_env)
        child_env["PIO_PROCESS_ID"] = str(rank)
        procs.append(subprocess.Popen(argv, env=child_env))
    logger.info(
        "launched %d process(es) for %r (coordinator %s)",
        num_processes,
        argv,
        coordinator_address,
    )
    deadline = _time.monotonic() + timeout if timeout else None
    rc = 0
    try:
        for p in procs:
            remaining = (
                max(0.1, deadline - _time.monotonic()) if deadline else None
            )
            try:
                code = p.wait(timeout=remaining)
            except subprocess.TimeoutExpired:
                rc = rc or 124
                break
            if code and not rc:
                rc = code
    finally:
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            if p.poll() is None:
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()
    return rc
