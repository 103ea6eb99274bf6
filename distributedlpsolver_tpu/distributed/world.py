"""Process-group world runtime — the multi-host half of parallel/.

The reference's distribution substrate is an MPI world spanning
machines; the JAX-native equivalent is ``jax.distributed.initialize``:
every process runs the same SPMD program, the coordinator (rank 0)
wires the processes into one runtime, and ``jax.devices()`` then
reports the GLOBAL accelerator set — ``pjit`` programs compiled against
a mesh over it run across all devices of every process (SNIPPETS.md
[2]/[3]). Nothing in the solver's math changes; placement and fetch go
through ``parallel.mesh.put_global`` / ``host_value``.

Env contract (set by distributed/launcher.py; identical on a real pod
where the per-host agent exports it):

    DLPS_COORDINATOR    host:port of the rank-0 coordination service
    DLPS_RANK           this process's rank (0-based)
    DLPS_WORLD_SIZE     total process count
    DLPS_LOCAL_DEVICES  devices per process (harness: virtual CPU devs)
    DLPS_HEARTBEAT_DIR  per-rank heartbeat files (death detection)
    DLPS_SLICE_ID       logical slice name (serving registration)
    DLPS_WORLD_GEN      world generation (0 = first launch; bumped by
                        every coordinator-level re-initialization)

Single-machine CPU harness: each process pins ``JAX_PLATFORMS=cpu`` +
``--xla_force_host_platform_device_count=K`` and the world initializes
gloo CPU collectives, so N processes × K virtual devices exercise the
REAL cross-process dataflow (per-process addressable shards, psum over
the process boundary) without a pod — the multi-host analogue of the
8-virtual-device conftest trick (SURVEY.md §4).

Death semantics (measured, jax 0.4.x): when one rank dies, XLA's
coordination service propagates a fatal error and TERMINATES every
surviving process — a jax.distributed world dies as a unit, and
in-process re-initialization over survivors is not possible. The
heartbeat files here exist to make that death FAST and ATTRIBUTABLE
(sub-second file-mtime staleness vs the coordination service's
multi-second timeout): each rank's monitor sees a stale peer and exits
deliberately, and the launcher-level supervisor (distributed/launcher.
WorldSupervisor) relaunches a smaller world from the checkpoint — the
coordinator-level rung of the recovery ladder.
"""

from __future__ import annotations

import dataclasses
import json
import os
import threading
import time
from typing import Callable, List, Optional, Sequence

import numpy as np

# Env keys — ONE definition for launcher, worker, cli and tests.
ENV_COORDINATOR = "DLPS_COORDINATOR"
ENV_RANK = "DLPS_RANK"
ENV_WORLD_SIZE = "DLPS_WORLD_SIZE"
ENV_LOCAL_DEVICES = "DLPS_LOCAL_DEVICES"
ENV_HEARTBEAT_DIR = "DLPS_HEARTBEAT_DIR"
ENV_SLICE_ID = "DLPS_SLICE_ID"
ENV_WORLD_GEN = "DLPS_WORLD_GEN"


@dataclasses.dataclass(frozen=True)
class WorldConfig:
    """One process's view of the world it should join."""

    coordinator: Optional[str] = None  # host:port; None = single-process
    rank: int = 0
    world_size: int = 1
    local_devices: int = 0  # 0 = whatever the platform reports
    heartbeat_dir: Optional[str] = None
    slice_id: Optional[str] = None
    generation: int = 0
    # Heartbeat cadence / staleness: a peer whose file has not moved for
    # ``heartbeat_ttl_s`` is presumed dead. The TTL is deliberately
    # ~15 periods: N ranks compiling XLA programs oversubscribe every
    # core of a harness machine and a writer thread can starve for many
    # seconds, and a false peer-loss kills the whole world (every rank
    # exits deliberately). The monitor is an ATTRIBUTION aid and
    # backstop — real deaths are usually propagated faster by the
    # coordination service's own fatal (and, on the harness, by the
    # launcher watching child exits directly) — so a generous TTL costs
    # little detection latency and buys stall immunity.
    heartbeat_period_s: float = 1.0
    heartbeat_ttl_s: float = 15.0
    # jax.distributed.initialize timeout (barrier at world formation).
    init_timeout_s: float = 60.0

    @classmethod
    def from_env(cls, env=os.environ) -> "WorldConfig":
        return cls(
            coordinator=env.get(ENV_COORDINATOR) or None,
            rank=int(env.get(ENV_RANK, "0")),
            world_size=int(env.get(ENV_WORLD_SIZE, "1")),
            local_devices=int(env.get(ENV_LOCAL_DEVICES, "0")),
            heartbeat_dir=env.get(ENV_HEARTBEAT_DIR) or None,
            slice_id=env.get(ENV_SLICE_ID) or None,
            generation=int(env.get(ENV_WORLD_GEN, "0")),
        )


def _die_on_peer_loss(world: "World", dead: List[int]) -> None:
    """Default peer-loss reaction: exit hard, immediately.

    The surviving processes of a jax.distributed world are dead anyway
    (the coordination service fatals them within seconds); exiting NOW,
    deliberately and with a distinct code, makes the whole-world death
    fast and lets the launcher's supervisor attribute it ("rank N went
    first") instead of parsing XLA's fatal log. os._exit skips atexit —
    a collective may be wedged on the dead peer and normal teardown
    would block behind it."""
    import sys

    print(
        f"[world] rank {world.rank}: peer rank(s) {dead} lost heartbeat — "
        f"world is dead, exiting",
        file=sys.stderr,
        flush=True,
    )
    os._exit(WORLD_PEER_LOST_EXIT)


# Exit code of a deliberate peer-loss exit — the launcher's supervisor
# distinguishes "this rank detected a dead peer" from "this rank was the
# original fault".
WORLD_PEER_LOST_EXIT = 43


class World:
    """A joined process group: rank/size, the global mesh, collectives,
    and the heartbeat threads."""

    def __init__(self, cfg: WorldConfig):
        import jax

        self.cfg = cfg
        self.rank = jax.process_index()
        self.world_size = jax.process_count()
        self._jax = jax
        self._hb_thread: Optional[threading.Thread] = None
        self._monitor_thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._closed = False

    # -- identity ---------------------------------------------------------

    @property
    def is_primary(self) -> bool:
        return self.rank == 0

    def describe(self) -> dict:
        jax = self._jax
        return {
            "rank": self.rank,
            "world_size": self.world_size,
            "generation": self.cfg.generation,
            "slice_id": self.cfg.slice_id,
            "local_devices": jax.local_device_count(),
            "global_devices": jax.device_count(),
            "platform": jax.default_backend(),
        }

    # -- mesh / collectives ----------------------------------------------

    def mesh(self, axis: str = "batch"):
        """1-D global mesh over every device of every process — the
        drop-in replacement for the single-process ``make_mesh()``:
        ``batch_sharding`` / ``col_sharding`` work unchanged on it, and
        device order (process-major) is identical on every rank, so jit
        cache keys agree across the world."""
        from distributedlpsolver_tpu.parallel import mesh as mesh_lib

        return mesh_lib.make_mesh(axis_names=(axis,))

    def barrier(self, tag: str = "world") -> None:
        if self.world_size <= 1:
            return
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(
            f"dlps:{tag}:{self.cfg.generation}"
        )

    def allgather(self, value) -> list:
        """Gather a small host value (scalar / 1-D list of numbers)
        from every rank; returns the rank-ordered list on ALL ranks.
        A collective — every rank must call it in the same order."""
        if self.world_size <= 1:
            return [value]
        from jax.experimental import multihost_utils

        arr = np.atleast_1d(np.asarray(value, dtype=np.float64))
        out = multihost_utils.process_allgather(arr)  # (world, k)
        out = np.asarray(out).reshape(self.world_size, -1)
        if np.ndim(value) == 0:
            return [float(v[0]) for v in out]
        return [list(map(float, v)) for v in out]

    def agree(self, value, what: str = "value") -> list:
        """Assert every rank holds the SAME ``value`` (the rank-0-gather
        agreement check, e.g. ``bucket_cache_size()`` across the world —
        a rank whose program cache diverged recompiled somewhere its
        peers did not). Returns the gathered list; raises on mismatch."""
        vals = self.allgather(value)
        if any(v != vals[0] for v in vals[1:]):
            raise AssertionError(
                f"world disagreement on {what}: per-rank values {vals}"
            )
        return vals

    # -- heartbeat --------------------------------------------------------

    def _hb_path(self, rank: int) -> str:
        return os.path.join(self.cfg.heartbeat_dir, f"rank{rank}.hb")

    def start_heartbeat(
        self,
        on_peer_loss: Optional[Callable[["World", List[int]], None]] = None,
    ) -> None:
        """Start the heartbeat writer (every rank) and the peer monitor.

        The writer refreshes ``rank<k>.hb`` every period; the monitor
        checks every peer's file each period and calls ``on_peer_loss``
        (default: deliberate fast exit — see module docstring) when one
        goes stale past the TTL. No-op without a heartbeat_dir; a
        single-process world runs the WRITER only (the launcher's
        supervisor reads the beat as its world-ready signal — a
        re-formed world of one still has to announce itself) and skips
        the pointless peer monitor."""
        if self.cfg.heartbeat_dir is None:
            return
        os.makedirs(self.cfg.heartbeat_dir, exist_ok=True)
        self._write_beat()  # first beat before anyone can monitor us
        self._stop.clear()
        self._hb_thread = threading.Thread(
            target=self._beat_loop, daemon=True, name="dlps-world-hb"
        )
        self._hb_thread.start()
        if self.world_size <= 1:
            return
        cb = on_peer_loss or _die_on_peer_loss
        self._monitor_thread = threading.Thread(
            target=self._monitor_loop,
            args=(cb,),
            daemon=True,
            name="dlps-world-monitor",
        )
        self._monitor_thread.start()

    def _write_beat(self) -> None:
        from distributedlpsolver_tpu.utils.logging import stamp_record

        path = self._hb_path(self.rank)
        tmp = f"{path}.{os.getpid()}.tmp"
        # Stamped like every other record a consumer may merge: the
        # launcher reads mtimes, but post-mortem tooling concatenates
        # beat files into the world's JSONL view and needs the shared
        # schema_version/ts/t_mono header.
        payload = json.dumps(
            stamp_record(
                {
                    "rank": self.rank,
                    "pid": os.getpid(),
                    "generation": self.cfg.generation,
                }
            )
        )
        try:
            with open(tmp, "w") as fh:
                fh.write(payload)
            os.replace(tmp, path)
        except OSError:
            pass  # a missed beat is recoverable; TTL ≥ 3 periods

    def _beat_loop(self) -> None:
        while not self._stop.wait(self.cfg.heartbeat_period_s):
            self._write_beat()

    def peer_staleness(self) -> dict:
        """rank -> seconds since that rank's last beat (inf = no file).
        Reads mtimes only; safe from any thread."""
        now = time.time()
        out = {}
        for r in range(self.world_size):
            if r == self.rank:
                continue
            try:
                out[r] = now - os.stat(self._hb_path(r)).st_mtime
            except OSError:
                out[r] = float("inf")
        return out

    def _monitor_loop(self, on_peer_loss) -> None:
        # Startup grace: peers may still be importing jax. A peer is only
        # monitored once its FIRST beat has been seen.
        seen: set = set()
        while not self._stop.wait(self.cfg.heartbeat_period_s):
            stale = self.peer_staleness()
            seen.update(r for r, s in stale.items() if s < np.inf)
            dead = sorted(
                r
                for r, s in stale.items()
                if r in seen and s > self.cfg.heartbeat_ttl_s
            )
            if dead:
                on_peer_loss(self, dead)
                return

    # -- teardown ---------------------------------------------------------

    def close(self) -> None:
        """Stop heartbeats and leave the process group (best-effort —
        the shutdown barrier needs every peer alive; a failed barrier
        after a peer death is expected and swallowed)."""
        if self._closed:
            return
        self._closed = True
        self._stop.set()
        for t in (self._hb_thread, self._monitor_thread):
            if t is not None:
                t.join(timeout=2.0)
        if self.world_size > 1:
            try:
                self._jax.distributed.shutdown()
            except Exception:
                pass


def init_world(cfg: Optional[WorldConfig] = None) -> World:
    """Join (or degenerate to) the configured world; returns the World.

    MUST run before anything initializes jax backends: the CPU
    collectives implementation and the distributed client both bind at
    backend-init time. ``world_size <= 1`` (no env, plain process) skips
    ``jax.distributed`` entirely — the same code path then runs
    single-process, the ``mpirun -np 1`` analogue.
    """
    cfg = cfg or WorldConfig.from_env()
    import jax

    if cfg.world_size > 1:
        if not cfg.coordinator:
            raise ValueError(
                f"world_size={cfg.world_size} needs a coordinator address "
                f"({ENV_COORDINATOR})"
            )
        # Cross-process CPU collectives (the single-machine harness):
        # gloo ships in jaxlib; without it every cross-process psum
        # would fail at dispatch.
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(
            coordinator_address=cfg.coordinator,
            num_processes=cfg.world_size,
            process_id=cfg.rank,
            initialization_timeout=int(cfg.init_timeout_s),
        )
    world = World(cfg)
    if world.world_size != cfg.world_size and cfg.world_size > 1:
        raise RuntimeError(
            f"world formed with {world.world_size} processes, expected "
            f"{cfg.world_size}"
        )
    return world


def world_from_env() -> World:
    """``init_world(WorldConfig.from_env())`` — the worker entry's one-liner."""
    return init_world(WorldConfig.from_env())
