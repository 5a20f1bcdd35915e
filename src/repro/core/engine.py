"""SummaryEngine: the single owner of Alg. 1, over a pluggable ``Backend``.

Before this module the repo carried three divergent copies of the paper's
merge→sparsify loop (``summarize()`` plus the ``make_distributed_step*``
builders), each re-implementing the θ schedule, stopping rule, budget
feasibility and finalize. The engine collapses them (DESIGN.md §12): it owns

  * the θ schedule — Eq. (21), θ(t) = (1+t)⁻¹ for t < T, 0 at t = T;
  * the stopping rule — Alg. 1 line 4 (``size_bits ≤ k``) plus convergence
    (θ = 0 and no merges accepted);
  * the ``ensure_budget`` feasibility rounds (DESIGN.md §4): extra θ = 0
    merges until the membership term |V|log₂|S| fits under k;
  * finalize — the Sect. 3.2.4 drop-to-k further sparsification,

while a :class:`Backend` supplies the three device-side primitives:

  * ``run_chunk``        — score/merge up to R rounds in one dispatch;
  * ``num_supernodes``   — |S| of a state (feasibility check);
  * ``sparsify_finalize``— the drop-to-k tail + exact Eq. (2)/(4) metrics.

**Chunked, device-resident driver.** ``run_chunk`` executes up to
``cfg.driver_chunk`` rounds inside one ``lax.while_loop`` dispatch: the
stopping predicate is evaluated on device each round, per-round scalar
stats land in an on-device [R]-buffer, and the host syncs only on chunk
boundaries — instead of a full device→host round-trip per iteration.
θ values are precomputed on the host (bit-identical to the historical
per-round python floats) and passed as an f32[R] operand. Because each
round runs exactly the same traced computation as the historical
one-round-per-dispatch driver, metrics are bit-identical for any chunk
size; ``driver_chunk=1`` recovers the historical host-synced driver
(benchmarks/fig8_iterations.py measures the difference).

Backends in-tree: :class:`LocalBackend` below (single device; the engine
behind ``repro.core.summarize``) and
``repro.core.distributed.make_distributed_backend`` (edge-sharded
shard_map, hash- or group-owner pair routing). Streaming summarization and
the query-serving layer plug in the same way: implement the three
primitives, reuse the loop.

**Fault tolerance (DESIGN.md §13).** Everything Alg. 1 needs to continue
from a chunk boundary is one replicated pytree (the ``SummaryState``:
supernode membership, sizes, rng, round counter) plus a small host-side
payload (θ-schedule position ``t_next`` — also the distributed salt
``t0`` —, the stopping flag, budget-loop position, phase marker, history,
and the config/graph fingerprints). :class:`EngineCheckpointer` saves that
through :class:`repro.runtime.checkpoint.CheckpointManager` — async,
atomic, keep-N — at the engine's host-sync points, and
:meth:`SummaryEngine.run` with ``resume=True`` validates the fingerprints
and continues *bit-identically*: each round is the same traced computation
wherever the chunk boundaries fall, so a killed-and-resumed run reproduces
the uninterrupted metrics exactly (``tests/chaos_check.py``). A
:class:`~repro.runtime.elastic.PreemptionGuard` polled at the same sync
points turns SIGTERM/SIGINT into save-and-raise
:class:`~repro.runtime.elastic.Preempted`.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Protocol

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import costs, merge, sparsify
from repro.core.types import (
    SummaryConfig,
    SummaryState,
    init_state,
    make_graph,
)
from repro.runtime.checkpoint import CheckpointManager
from repro.runtime.elastic import Preempted, PreemptionGuard
from repro.runtime.straggler import StragglerMonitor

# Per-round scalar stats of the local backend (fixed key set → fixed-shape
# on-device chunk buffers).
LOCAL_STAT_KEYS = (
    "size_bits",
    "mdl_cost",
    "re1",
    "re2",
    "nmerges",
    "num_supernodes",
    "num_superedges",
    "total_reduction",
)


def theta_schedule_host(t: int, big_t: int) -> float:
    """Eq. (21) on the host — the exact float the driver feeds round ``t``."""
    return 1.0 / (1.0 + t) if t < big_t else 0.0


def global_preempt(local: bool) -> bool:
    """OR a preemption flag across every process in the mesh.

    On a process-spanning mesh (DESIGN.md §15) a SIGTERM lands on each
    process at a *different* loop position; if one process raised
    :class:`Preempted` at sync point ``t`` while another had already
    dispatched chunk ``t+1``, the survivor would hang forever inside a
    collective. Agreeing on the flag at every sync point — itself a tiny
    collective — makes all processes take the same branch. Single-process
    runs return the local flag untouched (no jax call at all).
    """
    if jax.process_count() == 1:
        return bool(local)
    from jax.experimental import multihost_utils

    flags = multihost_utils.process_allgather(np.asarray(bool(local)))
    return bool(np.any(flags))


class Backend(Protocol):
    """Device-side primitives the engine drives (DESIGN.md §12)."""

    cfg: SummaryConfig
    num_nodes: int
    num_edges: int
    stat_keys: tuple[str, ...]

    def input_size_bits(self) -> float:
        """Size(G), Eq. (3) — the quantity budgets are fractions of."""
        ...

    def init(self) -> SummaryState:
        """Ḡ := G (Alg. 1 lines 1–2)."""
        ...

    def run_chunk(
        self, state: SummaryState, thetas: jax.Array, t0: int,
        k_bits: float, limit: int,
    ) -> tuple[SummaryState, dict[str, jax.Array], jax.Array]:
        """Up to ``limit`` merge rounds in one dispatch (``thetas[i]`` is
        round ``t0 + i``'s θ). Returns the new state, per-round stat
        buffers ``{key: f32[R]}``, and the number of rounds executed."""
        ...

    def num_supernodes(self, state: SummaryState) -> int:
        ...

    def sparsify_finalize(
        self, state: SummaryState, k_bits: float, salt: int
    ) -> dict[str, Any]:
        """Sect. 3.2.4 drop-to-k + final metrics; backend-shaped payload."""
        ...

    def state_sharding(self):
        """Target sharding for a restored ``SummaryState`` leaf (or ``None``
        for the default placement) — reshard-on-restore onto the *current*
        mesh, whatever shape the checkpoint was written under."""
        ...


# ---------------------------------------------------------------------------
# Checkpoint/resume of Alg. 1 state (DESIGN.md §13)
# ---------------------------------------------------------------------------


#: SummaryConfig fields excluded from the resume fingerprint: pure execution
#: scheduling with proven bit-identity across values (tests/test_engine.py,
#: tests/dist_check.py) — a run may legitimately resume with a different
#: chunking, e.g. after an elastic re-mesh retuned the dispatch size.
FINGERPRINT_EXEMPT = ("driver_chunk",)


def config_fingerprint(cfg: SummaryConfig) -> dict:
    """The config identity a checkpoint is only resumable under."""
    fp = dataclasses.asdict(cfg)
    for k in FINGERPRINT_EXEMPT:
        fp.pop(k, None)
    return fp


def graph_fingerprint(backend: Backend, extra: dict | None = None) -> dict:
    """Graph identity: |V|, |E| (and caller-supplied provenance, e.g. the
    CSR-cache source stamp). Deliberately mesh-independent — restoring onto
    a different device count is the elastic path, not a mismatch."""
    fp = {"num_nodes": int(backend.num_nodes),
          "num_edges": int(backend.num_edges)}
    if extra:
        fp.update(extra)
    return fp


class FingerprintMismatch(ValueError):
    """A checkpoint was written by a different config or graph."""


@dataclasses.dataclass
class EngineCheckpointer:
    """Chunk-boundary checkpointing policy around a CheckpointManager.

    ``every`` is the save cadence in *completed rounds*, aligned up to the
    engine's host-sync points (chunk boundaries) — with ``driver_chunk=8``
    and ``every=1`` a save still happens only every 8 rounds, because the
    host only holds a consistent state there. ``every <= 0`` disables
    periodic saves; the preemption save and the final ``phase="final"``
    save (merge loop done, only sparsify left) always happen.

    ``guard`` wires preemption in: polled at every sync point, and on a
    pending signal the engine saves synchronously (``wait`` on the async
    writer) and raises :class:`~repro.runtime.elastic.Preempted`.
    """

    manager: CheckpointManager
    every: int = 1
    guard: PreemptionGuard | None = None
    graph_extra: dict | None = None  # provenance merged into the graph fp

    def fingerprints(self, backend: Backend) -> dict:
        return {"config": config_fingerprint(backend.cfg),
                "graph": graph_fingerprint(backend, self.graph_extra)}

    def due(self, completed: int, last_saved: int) -> bool:
        return self.every > 0 and completed - last_saved >= self.every

    def save(self, backend: Backend, state: SummaryState, payload: dict,
             *, sync: bool = False) -> int:
        step = int(payload["t_next"]) - 1  # completed rounds
        # On a process-spanning mesh the Alg. 1 state is replicated, so
        # process 0 writes for everyone (all processes share the directory
        # — DESIGN.md §15); the others still count the save so the
        # `checkpoint_saves` bookkeeping stays identical across processes.
        if jax.process_count() > 1 and jax.process_index() != 0:
            return step
        extra = dict(payload, fingerprints=self.fingerprints(backend))
        self.manager.save_async(step, state, extra)
        if sync:
            self.manager.wait()
        return step

    def restore(self, backend: Backend):
        """Latest committed state or ``None`` (nothing committed yet).

        Returns ``(state, payload, step)``. Validates the config/graph
        fingerprints against ``backend`` and reshards every leaf onto the
        backend's current placement (``state_sharding``) — the 8→4-device
        elastic restore is this one ``device_put``, no resharding pass.
        """
        if self.manager.latest_step() is None:
            return None
        template = backend.init()
        sharding = backend.state_sharding()
        state, step, payload = self.manager.restore(
            template,
            sharding_fn=(None if sharding is None else (lambda _k: sharding)),
        )
        want = self.fingerprints(backend)
        got = payload.get("fingerprints", {})
        for kind in ("config", "graph"):
            if got.get(kind) != want[kind]:
                diff = {
                    k: (got.get(kind, {}).get(k), want[kind][k])
                    for k in set(want[kind]) | set(got.get(kind, {}))
                    if got.get(kind, {}).get(k) != want[kind].get(k)
                }
                raise FingerprintMismatch(
                    f"checkpoint step {step} in {self.manager.dir!r} was "
                    f"written under a different {kind}: "
                    f"{{field: (checkpoint, current)}} = {diff}")
        return state, payload, step

    def preempted(self) -> bool:
        return self.guard is not None and self.guard.preempted


@dataclasses.dataclass
class EngineRun:
    """Everything Alg. 1 produced, before backend-specific result assembly."""

    state: SummaryState
    history: list[dict]
    last_stats: dict | None  # stats of the last merge round (None if T=0)
    iterations_run: int
    input_size_bits: float
    k_bits: float
    finalize: dict[str, Any]  # backend payload from sparsify_finalize
    sparsify_wall_s: float
    # fault-tolerance / observability bookkeeping (DESIGN.md §13)
    chunk_wall_s: list = dataclasses.field(default_factory=list)
    straggler_events: list = dataclasses.field(default_factory=list)
    resumed_from: int | None = None  # checkpoint step this run restarted at
    checkpoint_saves: int = 0
    checkpoint_snapshot_wall_s: float = 0.0  # driver-thread stall, total


class SummaryEngine:
    """Alg. 1 against a :class:`Backend`; one loop for every execution mode."""

    def __init__(self, backend: Backend):
        self.backend = backend
        self.cfg = backend.cfg

    def _should_stop(self, stats: dict, theta: float, k_bits: float) -> bool:
        if stats["size_bits"] <= k_bits:
            return True
        # converged: θ=0 accepts any cost-reducing merge; none left
        return stats["nmerges"] == 0 and theta == 0.0

    @functools.partial(jax.profiler.annotate_function, name="ssumm.engine")
    def run(self, collect_history: bool = True, *,
            checkpointer: EngineCheckpointer | None = None,
            monitor: StragglerMonitor | None = None,
            resume: bool = False) -> EngineRun:
        """Drive Alg. 1 to the final summary (optionally crash-safe).

        With a ``checkpointer``, the replicated Alg. 1 state plus the
        host-side loop position is saved (async, atomic) at chunk
        boundaries, and ``resume=True`` continues a prior run from its
        latest committed checkpoint — bit-identical to never having
        stopped, because every round is the same traced computation
        regardless of where the chunk boundaries fall. A pending
        preemption signal (``checkpointer.guard``) is honored at the same
        sync points: save synchronously, raise
        :class:`~repro.runtime.elastic.Preempted`.

        ``monitor`` (a :class:`~repro.runtime.straggler.StragglerMonitor`)
        brackets every device dispatch with ``begin_step``/``end_step``;
        flagged events land in ``EngineRun.straggler_events`` and per-chunk
        wall times in ``EngineRun.chunk_wall_s``.

        The whole run is the host span ``ssumm.engine`` on a profiler trace.
        """
        cfg, backend = self.cfg, self.backend
        size_g = backend.input_size_bits()
        k_bits = cfg.target_bits(size_g)
        chunk = max(1, cfg.driver_chunk)
        ck = checkpointer

        history: list[dict] = []
        chunk_walls: list[float] = []
        last: dict | None = None
        stopped = False
        t = 1  # next round index == the distributed salt t0
        extra_done = 0  # budget-feasibility rounds already run
        phase = "loop"  # "loop" (merge/budget rounds left) | "final"
        resumed_from: int | None = None
        saves = 0
        last_saved = 0

        if resume:
            if ck is None:
                raise ValueError("resume=True requires a checkpointer")
            restored = ck.restore(backend)
            if restored is not None:
                state, payload, resumed_from = restored
                t = int(payload["t_next"])
                stopped = bool(payload["stopped"])
                extra_done = int(payload["extra_done"])
                phase = payload["phase"]
                last = payload["last_stats"]
                last_saved = t - 1
                if collect_history:
                    history = list(payload["history"])
            else:
                state = backend.init()
        else:
            state = backend.init()

        def run_rounds(state, t0: int, limit: int, thetas: list[float]):
            """One device dispatch of ≤ ``limit`` rounds; host-side unpack."""
            th = np.zeros((chunk,), np.float32)
            th[: len(thetas)] = np.asarray(thetas, np.float32)
            if monitor is not None:
                monitor.begin_step()
            t_disp = time.perf_counter()
            state, buf, rounds = backend.run_chunk(
                state, jnp.asarray(th), t0, k_bits, limit
            )
            rounds = int(rounds)
            buf = {k: np.asarray(v) for k, v in buf.items()}
            # the unpack above blocked on the dispatch — time is real work
            chunk_walls.append(time.perf_counter() - t_disp)
            if monitor is not None:
                monitor.end_step(t0)
            rows = [
                {k: float(buf[k][i]) for k in backend.stat_keys}
                for i in range(rounds)
            ]
            return state, rows

        def payload_now() -> dict:
            return {
                "t_next": t, "stopped": stopped, "extra_done": extra_done,
                "phase": phase, "last_stats": last,
                "history": history if collect_history else [],
            }

        def sync_point(state, *, force: bool = False) -> None:
            """Host-sync bookkeeping: periodic save + preemption poll."""
            nonlocal saves, last_saved
            if ck is None:
                return
            preempt = ck.preempted()
            if ck.guard is not None:
                preempt = global_preempt(preempt)
            if force or preempt or ck.due(t - 1, last_saved):
                step = ck.save(backend, state, payload_now(), sync=preempt)
                saves += 1
                last_saved = t - 1
                if preempt:
                    raise Preempted(step)

        while phase == "loop" and t <= cfg.T and not stopped:
            limit = min(chunk, cfg.T - t + 1)
            thetas = [theta_schedule_host(tt, cfg.T)
                      for tt in range(t, t + limit)]
            state, rows = run_rounds(state, t, limit, thetas)
            for i, row in enumerate(rows):
                last = row
                if collect_history:
                    history.append(dict(row, t=t + i, theta=thetas[i]))
            t += len(rows)
            last_theta = thetas[len(rows) - 1]
            stopped = self._should_stop(last, last_theta, k_bits)
            sync_point(state)

        # budget-feasibility loop (DESIGN.md §4): membership bits
        # |V|log₂|S| must fit under k before edge-dropping can finish.
        # Every break decision is either re-derivable from the restored
        # state (membership, s_now) or encoded in the checkpoint phase
        # (the nmerges==0 convergence break), so a resumed run walks the
        # exact same extra rounds as an uninterrupted one.
        if cfg.ensure_budget:
            v = backend.num_nodes
            while phase == "loop" and extra_done < cfg.max_extra_iters:
                s_now = backend.num_supernodes(state)
                membership = v * float(np.log2(max(s_now, 2)))
                if membership <= k_bits or s_now <= 2:
                    break
                state, rows = run_rounds(state, t, 1, [0.0])
                last = rows[0]
                if collect_history:
                    history.append(dict(rows[0], t=t, theta=0.0))
                t += 1
                extra_done += 1
                if last["nmerges"] == 0:
                    phase = "final"
                sync_point(state)
                if phase == "final":
                    break
        iterations_run = t - 1

        # merge work is done — one last save so a crash inside the
        # sparsify tail resumes straight to finalize, no re-merging
        if phase != "final":
            phase = "final"
            sync_point(state, force=True)

        # waits for the device, so that the finalize's time is its own (and
        # the ``ssumm.engine`` span's), not the first host copy's after it
        t_sp = time.perf_counter()
        finalize = jax.block_until_ready(
            backend.sparsify_finalize(state, k_bits, iterations_run + 1))
        sparsify_wall_s = time.perf_counter() - t_sp
        snapshot_wall = 0.0
        if ck is not None:
            ck.manager.wait()  # surface async write errors before returning
            snapshot_wall = sum(
                s["snapshot_wall_s"] or 0.0
                for s in ck.manager.save_stats.values())
        return EngineRun(
            state=state,
            history=history,
            last_stats=last,
            iterations_run=iterations_run,
            input_size_bits=size_g,
            k_bits=k_bits,
            finalize=finalize,
            sparsify_wall_s=sparsify_wall_s,
            chunk_wall_s=chunk_walls,
            straggler_events=list(monitor.events) if monitor else [],
            resumed_from=resumed_from,
            checkpoint_saves=saves,
            checkpoint_snapshot_wall_s=snapshot_wall,
        )


# ---------------------------------------------------------------------------
# Local (single-device) backend — the engine behind repro.core.summarize
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("cfg",))
def _local_chunk(src, dst, state, thetas, k_bits, limit, cfg: SummaryConfig):
    """≤ ``limit`` merge rounds in one ``lax.while_loop`` dispatch."""
    r = thetas.shape[0]
    buf0 = {k: jnp.zeros((r,), jnp.float32) for k in LOCAL_STAT_KEYS}

    def cond(carry):
        i, _state, done, _buf = carry
        return (i < limit) & ~done

    def body(carry):
        i, state, _done, buf = carry
        theta = thetas[i]
        new_state, stats = merge.merge_iteration(src, dst, state, cfg, theta)
        buf = {
            k: buf[k].at[i].set(stats[k].astype(jnp.float32))
            for k in LOCAL_STAT_KEYS
        }
        done = (stats["size_bits"] <= k_bits) | (
            (stats["nmerges"] == 0) & (theta == 0.0)
        )
        return i + 1, new_state, done, buf

    rounds, state, _done, buf = jax.lax.while_loop(
        cond, body, (jnp.int32(0), state, jnp.bool_(False), buf0)
    )
    return state, buf, rounds


@functools.partial(jax.jit, static_argnames=("cfg", "num_nodes", "num_edges"))
def _local_finalize(src, dst, state, k_bits, cfg: SummaryConfig,
                    num_nodes, num_edges):
    pt = costs.build_pair_table(src, dst, state)
    drop, after = sparsify.further_sparsify(
        pt,
        state,
        num_nodes,
        num_edges,
        k_bits,
        cbar_mode=cfg.cbar_mode,
        re_guard=cfg.re_guard,
        error_p=cfg.error_p,
    )
    return pt, after["keep"], after


class LocalBackend:
    """Single-device Alg. 1 primitives over an in-memory edge list."""

    stat_keys = LOCAL_STAT_KEYS

    def __init__(self, src, dst, num_nodes: int, cfg: SummaryConfig):
        self.graph, self.num_nodes = make_graph(src, dst, num_nodes)
        self.num_edges = self.graph.num_edges
        self.cfg = cfg

    def input_size_bits(self) -> float:
        return costs.input_size_bits(self.num_nodes, self.num_edges)

    def init(self) -> SummaryState:
        return init_state(self.num_nodes, self.cfg.seed)

    def run_chunk(self, state, thetas, t0, k_bits, limit):
        del t0  # local rounds draw their randomness from state.rng alone
        return _local_chunk(
            self.graph.src, self.graph.dst, state, thetas,
            jnp.float32(k_bits), jnp.int32(limit), self.cfg,
        )

    def num_supernodes(self, state) -> int:
        return int(jnp.sum(state.size > 0))

    def state_sharding(self):
        return None  # single device: default placement

    def sparsify_finalize(self, state, k_bits, salt) -> dict:
        del salt  # deterministic closed-form drop — no re-randomization
        pt, keep, after = _local_finalize(
            self.graph.src, self.graph.dst, state, k_bits, self.cfg,
            self.num_nodes, self.num_edges,
        )
        return {"pair_table": pt, "keep": keep, "after": after}
