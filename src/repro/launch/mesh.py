"""Production meshes + the multi-host bootstrap. Defined as FUNCTIONS so
importing this module never touches jax device state (the dry-run sets
XLA_FLAGS before any jax import).

:func:`bootstrap_distributed` is the one place the tree calls
``jax.distributed.initialize`` (DESIGN.md §15): it must run before the
first device query of the process, it is a no-op for single-process runs
(every existing entry point keeps working unchanged), and on the CPU
backend it switches the collectives implementation to one that can cross
a process boundary. After it returns, ``jax.devices()`` spans every
process and the planned mesh is a real process-spanning mesh — the same
``shard_map`` programs run unchanged, with gloo carrying the collectives
between hosts.
"""

from __future__ import annotations

import dataclasses
import os

from repro.dist.sharding import make_mesh

#: environment fallbacks for the bootstrap flags — one launch command can be
#: broadcast to every host with only these three variables differing.
COORDINATOR_ENV = "SSUMM_COORDINATOR"
NUM_PROCESSES_ENV = "SSUMM_NUM_PROCESSES"
PROCESS_ID_ENV = "SSUMM_PROCESS_ID"


@dataclasses.dataclass(frozen=True)
class DistributedInfo:
    """What :func:`bootstrap_distributed` resolved for this process."""

    initialized: bool
    coordinator: str | None
    process_count: int
    process_index: int

    @property
    def is_main(self) -> bool:
        return self.process_index == 0

    def asdict(self) -> dict:
        return dataclasses.asdict(self)


def _env_int(name: str) -> int | None:
    val = os.environ.get(name)
    return int(val) if val not in (None, "") else None


def bootstrap_distributed(coordinator: str | None = None,
                          num_processes: int | None = None,
                          process_id: int | None = None) -> DistributedInfo:
    """``jax.distributed.initialize`` with a single-process no-op fallback.

    Flag precedence: explicit arguments, then the ``SSUMM_COORDINATOR`` /
    ``SSUMM_NUM_PROCESSES`` / ``SSUMM_PROCESS_ID`` environment variables.
    With ``num_processes`` unset or 1 nothing is initialized and the run
    behaves exactly as before (local devices only). Otherwise all three
    values must resolve, and the call MUST happen before anything touches
    jax device state — ``jax.distributed.initialize`` cannot attach to an
    already-initialized backend.

    On the CPU backend the default collectives implementation cannot cross
    processes, so multi-process runs switch to gloo
    (``jax_cpu_collectives_implementation``) — measured bit-identical to
    the single-process reductions on the same global device count
    (tests/multihost_check.py). jax builds without that config knob simply
    skip it (their backends ship working cross-process collectives).
    """
    coordinator = coordinator or os.environ.get(COORDINATOR_ENV) or None
    if num_processes is None:
        num_processes = _env_int(NUM_PROCESSES_ENV)
    if process_id is None:
        process_id = _env_int(PROCESS_ID_ENV)
    if num_processes is None or num_processes <= 1:
        return DistributedInfo(initialized=False, coordinator=None,
                               process_count=1, process_index=0)
    if coordinator is None or process_id is None:
        raise ValueError(
            f"multi-process bootstrap needs --coordinator and --process-id "
            f"(or ${COORDINATOR_ENV}/${PROCESS_ID_ENV}) alongside "
            f"num_processes={num_processes}")

    import jax

    try:  # CPU: cross-process collectives need gloo (no-op elsewhere)
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except AttributeError:  # jax build without the knob
        pass
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=int(num_processes),
                               process_id=int(process_id))
    return DistributedInfo(initialized=True, coordinator=coordinator,
                           process_count=int(num_processes),
                           process_index=int(process_id))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 v5e pod (data, model) or 2 pods (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(shape=(2, 4), axes=("data", "model")):
    """Small mesh over host CPU devices (tests)."""
    return make_mesh(shape, axes)


def mesh_device_count(mesh) -> int:
    out = 1
    for v in mesh.shape.values():
        out *= v
    return out
