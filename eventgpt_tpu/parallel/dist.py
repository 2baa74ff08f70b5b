"""Multi-host distributed bootstrap — the NCCL/MPI analog.

The reference's communication backend was NCCL + optional MPI pulled in by
torch/DeepSpeed (``requirements.txt:85,65,21``); nothing in-tree. The TPU
equivalent is ``jax.distributed.initialize`` (one call per host process)
after which pjit-compiled collectives ride ICI within a slice and DCN across
slices with no explicit communication code (SURVEY.md §5 "Distributed
communication backend").

Environment contract (mirrors the torchrun/deepspeed launcher env vars):

  EGPT_COORDINATOR   coordinator address host:port (a la MASTER_ADDR/PORT)
  EGPT_NUM_PROCESSES total process count            (a la WORLD_SIZE)
  EGPT_PROCESS_ID    this process's rank            (a la RANK)

On TPU pods / GKE these are auto-detected by JAX and the variables may be
omitted entirely; ``initialize_distributed()`` is then a thin safe wrapper.
"""

from __future__ import annotations

import logging
import os
from typing import Optional

import jax

log = logging.getLogger("eventgpt_tpu.dist")

_INITIALIZED = False

# These are how a cloud/pod launcher feeds jax.distributed.initialize its
# coordination parameters. Exported so test harnesses that simulate
# standalone hosts scrub exactly this set (parallel/multiproc.py) — a
# private copy would drift.
POD_AUTODETECT_VARS = (
    "TPU_WORKER_HOSTNAMES", "TPU_SKYLARK_HOSTS",
    "MEGASCALE_COORDINATOR_ADDRESS",
)


def _pod_launch() -> bool:
    """True when the environment describes a multi-host launch. A
    single-host TPU VM exports ``TPU_WORKER_HOSTNAMES=localhost`` too (the
    v5e host of PR 21 does): one worker is not a pod, and sending that run
    into ``jax.distributed.initialize()`` with no arguments ends in
    "coordinator_address should be defined"."""
    workers = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    return any(v in os.environ for v in POD_AUTODETECT_VARS
               if v != "TPU_WORKER_HOSTNAMES") or "," in workers.strip(",")


def initialize_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> bool:
    """Bootstrap multi-host JAX. Returns True if a multi-process runtime was
    initialized, False for the single-process fast path.

    Safe to call repeatedly (idempotent) and safe to call in single-host
    runs: with no coordinator configured and no cloud autodetection
    available, it degrades to a no-op.
    """
    global _INITIALIZED
    if _INITIALIZED:
        return True

    coordinator_address = coordinator_address or os.environ.get("EGPT_COORDINATOR")
    if num_processes is None and "EGPT_NUM_PROCESSES" in os.environ:
        num_processes = int(os.environ["EGPT_NUM_PROCESSES"])
    if process_id is None and "EGPT_PROCESS_ID" in os.environ:
        process_id = int(os.environ["EGPT_PROCESS_ID"])

    explicit = coordinator_address is not None
    autodetectable = _pod_launch()
    if not explicit and not autodetectable:
        if num_processes is not None or process_id is not None:
            # Half-configured launch: running on silently would give N
            # independent single-process trainers all claiming primary.
            raise ValueError(
                "EGPT_NUM_PROCESSES/EGPT_PROCESS_ID are set but "
                "EGPT_COORDINATOR is not; refusing to fall back to a "
                "single-process run"
            )
        log.info("single-process run; skipping jax.distributed.initialize")
        return False

    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    _INITIALIZED = True
    log.info(
        "distributed runtime up: process %d/%d, %d local / %d global devices",
        jax.process_index(), jax.process_count(),
        jax.local_device_count(), jax.device_count(),
    )
    return True


def is_primary() -> bool:
    """True on the process that should write checkpoints / logs."""
    return jax.process_index() == 0


def barrier(name: str = "barrier") -> None:
    """Cross-host sync point (debug/checkpoint fencing)."""
    if jax.process_count() == 1:
        return
    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices(name)
