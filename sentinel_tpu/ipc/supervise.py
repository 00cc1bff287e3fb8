"""Engine supervision & warm hot-restart for the multi-process plane.

PR 13/14 made one engine process the spine for N worker processes —
and therefore the single point of failure: an engine death left every
worker serving *static* policy-snapshot verdicts forever. This module
closes the loop (the Envoy hot-restart lineage: warm handoff, not cold
start):

* the **supervisor** (this process) owns the named shared-memory
  segments and the cross-process primitives (the MPSC claim lock and
  the adaptive-wakeup doorbells — they cannot live in shared memory,
  so they must belong to a process that OUTLIVES any one engine);
* the **engine child** builds its Engine, loads rules (the ``setup``
  callable), warm-starts from the durable checkpoint
  (``sentinel.tpu.failover.checkpoint.path`` →
  ``FailoverManager.restore_durable``), then attaches an
  :class:`~sentinel_tpu.ipc.plane.IngestPlane` to the EXISTING rings —
  bumping the control header's engine-boot epoch;
* **workers** are ordinary worker-mode children: when the engine dies
  they serve the failover-policy snapshot, and when the epoch bumps
  they re-intern, re-assert their live-admission ledgers and replay
  buffered completions (ipc/worker.py reconnect protocol);
* a crashed engine child is respawned on the shared
  :class:`~sentinel_tpu.datasource.backoff.Backoff`
  (``sentinel.tpu.supervise.backoff.{ms,max.ms}``), bounded by
  ``sentinel.tpu.supervise.restarts.max`` (0 = unlimited).

PR 20 closes the cold-boot gap with a **warm standby** and a
**planned live handoff**:

* with ``sentinel.tpu.supervise.standby.enabled`` the supervisor
  pre-forks a SECOND engine child (``standby_main``) that imports JAX,
  loads rules, warm-compiles the flush kernels via probe batches
  (``FailoverManager.warm_probe``) and re-warms from the durable
  checkpoint every ``standby.warm.interval.ms`` — parked WITHOUT
  attaching to the rings. On primary death the supervisor sends it
  ``attach`` instead of cold-respawning: the measured outage collapses
  from cold-boot seconds to ≈ the detection window, and the NEXT
  standby is pre-forked immediately;
* ``EngineSupervisor.handoff()`` (SIGUSR1 / the ``handoff`` transport
  command) triggers a planned drain: the primary publishes HANDOFF on
  the control header (workers HOLD new admissions instead of serving
  policy verdicts), settles in-flight flushes, spills a final durable
  checkpoint, marks its capture segments orderly-closed and exits with
  ``EXIT_HANDOFF`` — the standby takes over with zero policy-served
  verdicts. This is the mechanism for rolling engine upgrades and
  rule-table recompiles served from the standby.

The public faces are ``api.run_engine_supervised`` (embedders) and
``tools/ipc_launch.py --supervise`` (CLI).
"""

from __future__ import annotations

import multiprocessing
import os
import signal
import threading
import time
from dataclasses import dataclass, field
from typing import List, Optional, Sequence

from sentinel_tpu.utils.config import config

# Exit code of an engine child that completed a PLANNED handoff drain:
# the watcher promotes the standby immediately — no backoff, no restart
# budget spent (an orderly drain is not a crash).
EXIT_HANDOFF = 42


@dataclass
class PlaneHandles:
    """Everything an engine child (and the worker channels) need to
    share one set of named segments across engine restarts. Picklable
    through ``multiprocessing`` spawn — the lock/semaphores travel via
    mp's own reduction, so every consumer must be a DESCENDANT of the
    process that built this (the supervisor)."""

    prefix: str
    workers_max: int
    ring_slots: int
    slot_bytes: int
    resp_slots: int
    n_workers: int
    request_lock: object = field(repr=False, default=None)
    request_doorbell: object = field(repr=False, default=None)
    response_doorbells: Optional[List[object]] = field(
        repr=False, default=None
    )

    def channel(self, worker_id: int):
        """The worker-side attach record for one slot — the supervised
        twin of ``IngestPlane.channel`` (names are deterministic, so no
        plane object is needed here)."""
        from sentinel_tpu.ipc.worker import PlaneChannel

        bells = self.response_doorbells or []
        return PlaneChannel(
            control_name=f"{self.prefix}-ctl",
            request_name=f"{self.prefix}-req",
            response_name=f"{self.prefix}-resp{worker_id}",
            ring_slots=self.ring_slots,
            slot_bytes=self.slot_bytes,
            resp_slots=self.resp_slots,
            workers_max=self.workers_max,
            request_lock=self.request_lock,
            request_doorbell=self.request_doorbell,
            response_doorbell=(
                bells[worker_id] if worker_id < len(bells) else None
            ),
        )


def make_handles(ctx, prefix: str, n_workers: int) -> PlaneHandles:
    """Build the shared primitives from the current config (the
    supervisor side; geometry keys replay into every child)."""
    wake = (config.get(config.IPC_WAKEUP) or "sleep").strip().lower()
    adaptive = wake == "adaptive"
    workers_max = max(1, config.get_int(config.IPC_WORKERS_MAX, 8))
    return PlaneHandles(
        prefix=prefix,
        workers_max=workers_max,
        ring_slots=config.get_int(config.IPC_RING_SLOTS, 1024),
        slot_bytes=max(1024, config.get_int(config.IPC_SLOT_BYTES, 16384)),
        resp_slots=config.get_int(config.IPC_RESP_SLOTS, 1024),
        n_workers=max(0, min(n_workers, workers_max)),
        request_lock=ctx.Lock(),
        request_doorbell=ctx.Semaphore(0) if adaptive else None,
        response_doorbells=(
            [ctx.Semaphore(0) for _ in range(workers_max)]
            if adaptive else None
        ),
    )


def _unlink_stale(name: str) -> None:
    """Remove a leftover segment from a DEAD supervisor incarnation.
    Safe by construction: the engine child and all workers are daemon
    children of the supervisor, so a crashed supervisor takes its whole
    fleet with it — nothing live can still be mapped to these names."""
    from multiprocessing import shared_memory

    try:
        s = shared_memory.SharedMemory(name)
    except (FileNotFoundError, OSError, ValueError):
        return
    try:
        s.close()
        s.unlink()
    except OSError:
        pass


def create_segments(handles: PlaneHandles):
    """Pre-create every named segment from the SUPERVISOR so (a) they
    outlive any one engine process and (b) workers can attach before
    the first engine is even up. A segment left behind by a CRASHED
    supervisor (its own kill -9 is inside this PR's failure domain) is
    unlinked and recreated fresh — the old fleet died with it. Returns
    the owner objects — keep them alive; ``destroy_segments`` unlinks
    at final shutdown."""
    from sentinel_tpu.ipc.ring import ControlBlock, ShmRing

    def fresh(factory, name):
        try:
            return factory()
        except FileExistsError:
            _unlink_stale(name)
            return factory()

    segs = [fresh(
        lambda: ControlBlock(
            f"{handles.prefix}-ctl", handles.workers_max, create=True
        ),
        f"{handles.prefix}-ctl",
    )]
    segs.append(fresh(
        lambda: ShmRing(
            f"{handles.prefix}-req", handles.ring_slots,
            handles.slot_bytes, create=True,
        ),
        f"{handles.prefix}-req",
    ))
    for wid in range(handles.n_workers):
        name = f"{handles.prefix}-resp{wid}"
        segs.append(fresh(
            lambda name=name: ShmRing(
                name, handles.resp_slots, handles.slot_bytes, create=True
            ),
            name,
        ))
    return segs


def destroy_segments(segs) -> None:
    for s in segs:
        try:
            s.destroy()
        except Exception:
            pass


def engine_main(handles: PlaneHandles, overrides, setup, setup_args) -> None:
    """Spawn target: one engine child's whole life. Top-level so
    ``multiprocessing`` spawn children import it by name.

    Order matters: rules first (``setup``), then the durable
    warm-start (restore wants the rule indexes compiled so the
    fingerprints can match), and the plane LAST — workers reconnect
    only once the warm state is installed, so their ledger
    re-assertions land on the restored world, never a half-built one."""
    for k, v in (overrides or {}).items():
        config.set(k, v)
    # This child constructs its plane explicitly from the handles — a
    # replayed ipc.enabled=true must not auto-start a second, anonymous
    # plane inside Engine.__init__.
    config.set(config.IPC_ENABLED, "false")
    from sentinel_tpu.core import api
    from sentinel_tpu.ipc.plane import IngestPlane
    from sentinel_tpu.utils.backend import enable_compile_cache, require_platform
    from sentinel_tpu.utils.record_log import record_log

    require_platform()
    enable_compile_cache()

    stop = threading.Event()

    def _on_term(_sig, _frm):
        stop.set()

    signal.signal(signal.SIGTERM, _on_term)
    signal.signal(signal.SIGINT, _on_term)

    eng = api.get_engine()
    if setup is not None:
        # A failed setup fails the child (non-zero exit): the watcher's
        # restart path owns the retry, never a rule-less engine.
        setup(eng, *(setup_args or ()))
    if eng.failover.armed and eng.failover.durable_path:
        try:
            eng.failover.restore_durable()
        except Exception:
            # restore_durable itself never raises by contract; this is
            # the last-resort guard — a warm start is an optimization,
            # never a liveness requirement.
            record_log.error(
                "[supervise] durable restore raised — cold start",
                exc_info=True,
            )
    IngestPlane(eng, handles=handles)
    record_log.info(
        "[supervise] engine child up (pid %d, epoch %d)",
        os.getpid(), eng.ipc_plane.engine_epoch,
    )
    raise SystemExit(_serve(eng, stop))


def _serve(eng, stop: threading.Event) -> int:
    """Park an ATTACHED engine child until shutdown. Returns the
    process exit code: 0 for an orderly SIGTERM close, ``EXIT_HANDOFF``
    after a planned handoff drain (SIGUSR1 or the ``handoff`` transport
    command) — the watcher promotes the warm standby on that code
    without touching the restart backoff."""
    handoff_evt = threading.Event()

    def _on_usr1(_sig, _frm):
        handoff_evt.set()

    try:
        signal.signal(signal.SIGUSR1, _on_usr1)
    except (ValueError, OSError):
        pass
    requested = getattr(eng, "handoff_requested", None)
    while not stop.is_set():
        if handoff_evt.is_set() or (
            requested is not None and requested.is_set()
        ):
            _perform_handoff(eng)
            return EXIT_HANDOFF
        stop.wait(0.2)
    eng.close()
    return 0


def _perform_handoff(eng) -> None:
    """The old-world half of a planned handoff, in drain order:
    (1) arm a one-shot checkpoint so the settling flush carries the
    freshest state; (2) ``plane.handoff()`` — publish HANDOFF (workers
    hold), drain the request ring to sustained-empty, detach WITHOUT
    the CLOSED word so the rings and worker ledgers survive for the
    successor; (3) settle in-flight flushes; (4) spill the final
    durable checkpoint synchronously; (5) mark the capture segments
    orderly-closed so the successor's death sweep files them as
    ``frozen-close-*``, not ``frozen-death-*``; (6) close the engine
    (the plane is already detached — no CLOSED is ever published)."""
    from sentinel_tpu.utils.record_log import record_log

    fo = eng.failover
    durable = fo.armed and fo.durable_path
    if durable:
        fo.request_checkpoint()
    plane = eng.ipc_plane
    if plane is not None:
        try:
            stats = plane.handoff()
            record_log.info("[supervise] handoff drain: %s", stats)
        except Exception:
            record_log.error(
                "[supervise] handoff drain failed — closing anyway",
                exc_info=True,
            )
    try:
        eng.flush()
        eng.drain()
    except Exception:
        record_log.error(
            "[supervise] handoff settle failed", exc_info=True
        )
    if durable:
        fo.spill_durable_now()
    if eng.capture is not None:
        try:
            eng.capture.mark_orderly_close("handoff")
        except Exception:
            record_log.error(
                "[supervise] orderly-close marker failed", exc_info=True
            )
    eng.close()


def standby_main(
    handles: PlaneHandles, overrides, setup, setup_args, conn
) -> None:
    """Spawn target: a warm STANDBY engine child. It does everything
    ``engine_main`` does EXCEPT attach: import JAX, load rules,
    warm-start from the durable checkpoint, warm-compile the flush
    kernels via probe batches — then park, re-warming from the durable
    file every ``standby.warm.interval.ms`` until the supervisor sends
    ``attach`` (primary died or drained), at which point it does a
    final restore, re-arms the flight recorder and attaches to the
    existing rings (boot-epoch bump → normal worker reconnect).

    Pipe protocol: child sends ``("ready", warm_boot_ms)`` once
    compiled, ``("attached", attach_ms)`` after the plane is up;
    parent sends ``"attach"`` or ``"stop"``. The flight recorder stays
    DISARMED until promotion — a standby's CaptureJournal would run
    the next-boot death sweep against the LIVE primary's segments in
    the shared capture directory."""
    for k, v in (overrides or {}).items():
        config.set(k, v)
    config.set(config.IPC_ENABLED, "false")
    cap_override = (overrides or {}).get(config.CAPTURE_ENABLED, "")
    config.set(config.CAPTURE_ENABLED, "false")
    from sentinel_tpu.core import api
    from sentinel_tpu.ipc.plane import IngestPlane
    from sentinel_tpu.utils.backend import enable_compile_cache, require_platform
    from sentinel_tpu.utils.record_log import record_log

    require_platform()
    enable_compile_cache()

    stop = threading.Event()

    def _on_term(_sig, _frm):
        stop.set()

    signal.signal(signal.SIGTERM, _on_term)
    signal.signal(signal.SIGINT, _on_term)

    t0 = time.perf_counter()
    eng = api.get_engine()
    if setup is not None:
        setup(eng, *(setup_args or ()))
    warm_s = max(
        0.05,
        config.get_int(config.SUPERVISE_STANDBY_WARM_MS, 2000) / 1e3,
    )

    def _rewarm() -> None:
        if eng.failover.armed and eng.failover.durable_path:
            try:
                eng.failover.restore_durable()
            except Exception:
                record_log.error(
                    "[standby] durable re-warm raised — keeping last "
                    "state", exc_info=True,
                )

    _rewarm()
    # The restore path may have probed already (try_recover); this
    # guarantees the jit cache is populated even when failover is
    # unarmed or the durable file does not exist yet. A failed probe
    # fails the child: a standby that never compiled is not warm.
    eng.failover.warm_probe()
    warm_boot_ms = (time.perf_counter() - t0) * 1e3
    try:
        conn.send(("ready", warm_boot_ms))
    except (OSError, ValueError, BrokenPipeError):
        return
    record_log.info(
        "[standby] warm and parked (pid %d, %.0f ms boot)",
        os.getpid(), warm_boot_ms,
    )
    while not stop.is_set():
        try:
            msg = conn.recv() if conn.poll(warm_s) else None
        except (EOFError, OSError):
            return  # supervisor died — the fleet dies with it
        if msg == "stop":
            eng.close()
            return
        if msg != "attach":
            if msg is None:
                _rewarm()
            continue
        # Promotion: final warm pass, re-arm the flight recorder (its
        # death sweep now runs AFTER the predecessor stopped writing
        # and honors the orderly-close marker), attach LAST so worker
        # re-assertions land on the restored world.
        _rewarm()
        if cap_override:
            config.set(config.CAPTURE_ENABLED, cap_override)
            try:
                from sentinel_tpu.runtime.capture import maybe_build_capture

                eng.capture = maybe_build_capture(eng)
            except Exception:
                record_log.error(
                    "[standby] capture re-arm failed — serving without "
                    "the flight recorder", exc_info=True,
                )
        t_att = time.perf_counter()
        IngestPlane(eng, handles=handles)
        attach_ms = (time.perf_counter() - t_att) * 1e3
        try:
            conn.send(("attached", attach_ms))
        except (OSError, ValueError, BrokenPipeError):
            pass
        record_log.info(
            "[standby] took over (pid %d, epoch %d, attach %.1f ms)",
            os.getpid(), eng.ipc_plane.engine_epoch, attach_ms,
        )
        raise SystemExit(_serve(eng, stop))
    eng.close()


class EngineSupervisor:
    """Keeps one engine child alive on the shared rings (see module
    doc). ``kill_engine()`` is the chaos hook the tests and the bench
    outage measurement use."""

    def __init__(
        self,
        setup=None,
        setup_args: Sequence[object] = (),
        n_workers: int = 0,
        prefix: Optional[str] = None,
    ) -> None:
        from sentinel_tpu.datasource.backoff import Backoff

        self.standby_enabled = config.get_bool(config.SUPERVISE_STANDBY, False)
        if self.standby_enabled:
            from sentinel_tpu.utils.backend import expects_tpu

            if expects_tpu():
                raise RuntimeError(
                    "sentinel.tpu.supervise.standby.enabled=true on a TPU "
                    "host: the warm standby is a second engine process, and "
                    "a chip serves one process at a time, so the standby "
                    "cannot hold the chip while the primary lives. Disable "
                    "the standby here."
                )
        self._ctx = multiprocessing.get_context("spawn")
        if prefix is None:
            prefix = (config.get(config.IPC_SHM_PREFIX) or "").strip()
        if not prefix:
            prefix = f"stpu-{os.getpid()}-{int(time.time() * 1000) & 0xFFFFFF:x}"
        self.prefix = prefix
        # Children replay the runtime config; the prefix must be in it
        # so any path that re-reads config agrees on the names.
        config.set(config.IPC_SHM_PREFIX, prefix)
        self.handles = make_handles(self._ctx, prefix, n_workers)
        self._segs = create_segments(self.handles)
        self._setup = setup
        self._setup_args = tuple(setup_args or ())
        self._overrides = config.runtime_snapshot("sentinel.tpu.")
        self.restarts = 0
        self.restarts_max = max(
            0, config.get_int(config.SUPERVISE_RESTARTS_MAX, 0)
        )
        self._backoff = Backoff(
            base_s=max(1, config.get_int(config.SUPERVISE_BACKOFF_MS, 500))
            / 1e3,
            cap_s=max(
                1, config.get_int(config.SUPERVISE_BACKOFF_MAX_MS, 10000)
            ) / 1e3,
        )
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self.gave_up = False
        # Warm standby (sentinel.tpu.supervise.standby.enabled): one
        # pre-forked, compile-warmed engine child parked off-plane; on
        # primary death (or planned handoff) it attaches instead of a
        # cold respawn, and the NEXT standby is pre-forked immediately.
        self.standby_takeovers = 0
        self.handoffs = 0
        self.standby_warm_boot_ms: Optional[float] = None
        self.standby_attach_ms: Optional[float] = None
        self._standby: Optional[dict] = None
        # Promoted standbys keep their pipe alive here (the reader
        # thread still consumes the "attached" ack after promotion).
        self._retired: List[dict] = []
        self._proc = self._spawn_engine()
        if self.standby_enabled:
            self._standby = self._spawn_standby()
        self._watcher = threading.Thread(
            target=self._watch, name="sentinel-supervisor", daemon=True
        )
        self._watcher.start()

    # -- lifecycle ------------------------------------------------------
    def _spawn_engine(self):
        p = self._ctx.Process(
            target=engine_main,
            args=(self.handles, self._overrides, self._setup,
                  self._setup_args),
            daemon=True,
        )
        p.start()
        return p

    def _spawn_standby(self) -> dict:
        parent, child = self._ctx.Pipe()
        p = self._ctx.Process(
            target=standby_main,
            args=(self.handles, self._overrides, self._setup,
                  self._setup_args, child),
            daemon=True,
        )
        p.start()
        child.close()
        sb = {
            "proc": p, "conn": parent,
            "ready": threading.Event(), "attached": threading.Event(),
            "warm_ms": None, "attach_ms": None,
        }
        t = threading.Thread(
            target=self._standby_reader, args=(sb,),
            name="sentinel-standby-reader", daemon=True,
        )
        t.start()
        return sb

    def _standby_reader(self, sb: dict) -> None:
        """Owns all RECEIVES on one standby's pipe (sends may come
        from any thread) — runs until the child closes its end."""
        while True:
            try:
                msg = sb["conn"].recv()
            except (EOFError, OSError):
                return
            if not (isinstance(msg, tuple) and msg):
                continue
            if msg[0] == "ready":
                sb["warm_ms"] = msg[1]
                sb["ready"].set()
            elif msg[0] == "attached":
                sb["attach_ms"] = msg[1]
                sb["attached"].set()

    def _promote_standby(self, planned: bool, timeout_s: float = 180.0) -> bool:
        """Hand the rings to the warm standby: wait for its ready
        report (a standby still compiling is STILL faster than a cold
        respawn — its boot is already in progress), send ``attach``,
        adopt it as the serving child and pre-fork the next standby.
        False (→ caller falls back to the cold-respawn path) when no
        live standby exists."""
        from sentinel_tpu.utils.record_log import record_log

        sb = self._standby
        self._standby = None
        if sb is None:
            return False
        proc = sb["proc"]
        deadline = time.monotonic() + timeout_s
        while (
            proc.is_alive()
            and not sb["ready"].is_set()
            and time.monotonic() < deadline
            and not self._stop.is_set()
        ):
            time.sleep(0.01)
        if not proc.is_alive() or not sb["ready"].is_set():
            record_log.warn(
                "[supervise] standby unusable (alive=%s ready=%s) — "
                "falling back to cold respawn", proc.is_alive(),
                sb["ready"].is_set(),
            )
            if proc.is_alive():
                proc.terminate()
            try:
                sb["conn"].close()
            except OSError:
                pass
            return False
        try:
            sb["conn"].send("attach")
        except (OSError, ValueError, BrokenPipeError):
            return False
        self.standby_warm_boot_ms = sb["warm_ms"]
        with self._lock:
            self._proc = proc
        self._retired.append(sb)
        if sb["attached"].wait(timeout_s):
            self.standby_attach_ms = sb["attach_ms"]
        record_log.info(
            "[supervise] standby promoted (pid %d, %s, warm boot "
            "%.0f ms)", proc.pid, "planned handoff" if planned else
            "crash takeover", sb["warm_ms"] or -1.0,
        )
        self._standby = self._spawn_standby()
        return True

    def _watch(self) -> None:
        from sentinel_tpu.utils.record_log import record_log

        spawned_at = time.monotonic()
        while not self._stop.is_set():
            with self._lock:
                p = self._proc
            p.join(timeout=0.2)
            if p.is_alive():
                # A child that stayed up past the backoff cap ran
                # healthy: reset the streak so the NEXT incident pays
                # the base delay, not the accumulated lifetime cap
                # (crash-loop protection is per incident, not forever).
                if (
                    self._backoff.failures
                    and time.monotonic() - spawned_at > self._backoff.cap
                ):
                    self._backoff.reset()
                continue
            if self._stop.is_set():
                continue
            planned = p.exitcode == EXIT_HANDOFF
            if self.standby_enabled and self._promote_standby(planned):
                # A takeover is not a restart: the budget and the
                # backoff streak meter crash LOOPS of the cold path,
                # and a planned drain is not a crash at all.
                if planned:
                    self.handoffs += 1
                else:
                    self.standby_takeovers += 1
                spawned_at = time.monotonic()
                continue
            if (
                self.restarts_max
                and self.restarts >= self.restarts_max
            ):
                self.gave_up = True
                record_log.error(
                    "[supervise] engine died (exit %s) and the restart "
                    "budget (%d) is spent — giving up; workers stay on "
                    "the policy snapshot", p.exitcode, self.restarts_max,
                )
                return
            delay = self._backoff.next_delay()
            record_log.warn(
                "[supervise] engine died (exit %s) — restarting in "
                "%.2fs (restart #%d)", p.exitcode, delay,
                self.restarts + 1,
            )
            if self._stop.wait(delay):
                return
            with self._lock:
                if self._stop.is_set():
                    return
                self.restarts += 1
                self._proc = self._spawn_engine()
            spawned_at = time.monotonic()

    def spawn_context(self):
        """The supervisor's (spawn) mp context — queues for worker
        targets must come from here so they travel to descendants."""
        return self._ctx

    def spawn_worker(self, target, worker_id: int, args: Sequence[object] = ()):
        """One worker-mode child on slot ``worker_id`` (the supervised
        twin of ``api.run_workers``'s per-worker spawn; the supervisor
        owns the id space, so slots are assigned, not claimed)."""
        from sentinel_tpu.ipc import worker_mode

        p = self._ctx.Process(
            target=worker_mode.worker_main,
            args=(self.handles.channel(worker_id), worker_id,
                  self._overrides, target, tuple(args)),
            daemon=True,
        )
        p.start()
        return p

    # -- observability / chaos -----------------------------------------
    def engine_pid(self) -> Optional[int]:
        with self._lock:
            return self._proc.pid if self._proc.is_alive() else None

    def alive(self) -> bool:
        with self._lock:
            return self._proc.is_alive()

    def kill_engine(self) -> Optional[int]:
        """SIGKILL the current engine child (chaos/testing): no
        cleanup, no CLOSED word — exactly the failure the supervisor
        exists for. Returns the killed pid (None when already down)."""
        with self._lock:
            p = self._proc
        if not p.is_alive() or p.pid is None:
            return None
        os.kill(p.pid, signal.SIGKILL)
        return p.pid

    def wait_standby_ready(self, timeout_s: float = 180.0) -> bool:
        """Block until the CURRENT standby reports warm (rules
        loaded, kernels compiled, durable state restored). False when
        standby mode is off or the report never arrives."""
        sb = self._standby
        if sb is None:
            return False
        return sb["ready"].wait(timeout_s)

    def handoff(self, timeout_s: float = 120.0) -> bool:
        """Operator-triggered planned handoff (rolling upgrade /
        rule-table recompile served from standby): SIGUSR1 the serving
        engine — it drains (workers HOLD on the HANDOFF word), spills
        a final durable checkpoint and exits ``EXIT_HANDOFF``; the
        watcher promotes the warm standby. True once a DIFFERENT
        engine child is serving a fresh heartbeat."""
        with self._lock:
            p = self._proc
        if not p.is_alive() or p.pid is None:
            return False
        old_pid = p.pid
        try:
            os.kill(old_pid, signal.SIGUSR1)
        except OSError:
            return False
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline and not self._stop.is_set():
            with self._lock:
                cur = self._proc
            if (
                cur.pid != old_pid
                and cur.is_alive()
                and self.wait_engine_up(timeout_s=1.0)
            ):
                return True
            time.sleep(0.02)
        return False

    def wait_engine_up(self, timeout_s: float = 120.0) -> bool:
        """Block until the CURRENT engine child publishes a heartbeat
        (control header wall-ms fresh) — readiness, not liveness."""
        from sentinel_tpu.ipc.ring import ControlBlock, HEALTH_CLOSED, _wall_ms

        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            try:
                ctl = ControlBlock(
                    f"{self.prefix}-ctl", self.handles.workers_max
                )
            except (OSError, ValueError):
                time.sleep(0.05)
                continue
            try:
                _epoch, health, _gen, wall = ctl.engine_view()
            finally:
                ctl.close()
            if wall and health != HEALTH_CLOSED and _wall_ms() - wall < 1000:
                return True
            time.sleep(0.05)
        return False

    def stop(self, timeout_s: float = 10.0) -> None:
        """Graceful shutdown: stop supervising, SIGTERM the engine
        child (it closes its engine cleanly), then unlink the
        segments."""
        self._stop.set()
        with self._lock:
            p = self._proc
        if p.is_alive() and p.pid is not None:
            try:
                os.kill(p.pid, signal.SIGTERM)
            except OSError:
                pass
        p.join(timeout_s)
        if p.is_alive():
            p.terminate()
            p.join(5.0)
        self._watcher.join(timeout=5.0)
        sb = self._standby
        self._standby = None
        if sb is not None:
            try:
                sb["conn"].send("stop")
            except (OSError, ValueError, BrokenPipeError):
                pass
            sb["proc"].join(timeout_s)
            if sb["proc"].is_alive():
                sb["proc"].terminate()
                sb["proc"].join(5.0)
            try:
                sb["conn"].close()
            except OSError:
                pass
        destroy_segments(self._segs)
        self._segs = []


def measure_restart_outage(
    setup,
    resource: str,
    prefix: Optional[str] = None,
    timeout_s: float = 180.0,
    entry_timeout_ms: int = 3000,
) -> dict:
    """The zero→kill→recover cycle as one measurement (shared by the
    bench ``ipc`` stage's ``restart_outage_ms`` column, the
    ``ipc_launch --smoke`` restart phase, and the chaos tests): start a
    supervised engine, probe from an IngestClient in THIS process until
    it serves device-backed verdicts, ``kill -9`` the engine child, and
    time how long callers stay on policy verdicts until the restarted
    engine serves again. Raises on no-recovery; callers treat that as a
    failed check."""
    from sentinel_tpu.ipc.worker import IngestClient

    sup = EngineSupervisor(setup=setup, n_workers=1, prefix=prefix)
    cli = None
    try:
        if not sup.wait_engine_up(timeout_s):
            raise RuntimeError("supervised engine never came up")
        cli = IngestClient(sup.handles.channel(0), 0)
        deadline = time.monotonic() + timeout_s
        while True:
            v = cli.entry(resource, timeout_ms=entry_timeout_ms)
            if v.admitted and not v.degraded:
                cli.exit(resource)
                break
            if time.monotonic() > deadline:
                raise RuntimeError("engine never served a live verdict")
            time.sleep(0.02)
        killed_pid = sup.kill_engine()
        t0 = time.monotonic()
        saw_dead = False
        policy_served = 0
        while time.monotonic() - t0 < timeout_s:
            v = cli.entry(resource, timeout_ms=entry_timeout_ms)
            if v.degraded or not v.admitted:
                # Policy-served (engine read dead) or the dead-world
                # frame's gen-gated shed from the NEW plane — both are
                # the outage window from the caller's seat.
                saw_dead = True
                policy_served += 1
            elif v.admitted:
                cli.exit(resource)
                if saw_dead:
                    outage_ms = (time.monotonic() - t0) * 1e3
                    # The reconnect (ledger re-assert) rides the beat
                    # loop and may land a tick AFTER the first live
                    # verdict — give it a moment so the returned count
                    # is deterministic for the chaos assertions.
                    grace = time.monotonic() + 10.0
                    while (
                        cli.counters.get("reconnects", 0) == 0
                        and time.monotonic() < grace
                    ):
                        time.sleep(0.05)
                    return {
                        "outage_ms": outage_ms,
                        "policy_served": policy_served,
                        "restarts": sup.restarts,
                        "reconnects": cli.counters.get("reconnects", 0),
                        "killed_pid": killed_pid,
                    }
            time.sleep(0.002)
        raise RuntimeError(
            f"no recovery within {timeout_s}s (restarts={sup.restarts})"
        )
    finally:
        if cli is not None:
            cli.close()
        sup.stop()


def measure_standby_outage(
    setup,
    resource: str,
    prefix: Optional[str] = None,
    timeout_s: float = 180.0,
    entry_timeout_ms: int = 30000,
) -> dict:
    """``measure_restart_outage`` with a warm standby armed: the same
    zero→kill→recover cycle, but the supervisor promotes the
    pre-forked standby instead of cold-booting — the measured outage is
    ≈ the detection window (`ipc.engine.dead.ms`), with the JAX-import
    and first-compile terms gone from the outage path. The caller must
    have set ``sentinel.tpu.supervise.standby.enabled`` (raises
    otherwise — measuring the cold path under this name would report a
    lie). Shared by the bench ``standby_outage_ms`` column, the
    ``ipc_launch --smoke`` standby phase, and the chaos tests."""
    from sentinel_tpu.ipc.worker import IngestClient

    sup = EngineSupervisor(setup=setup, n_workers=1, prefix=prefix)
    if not sup.standby_enabled:
        sup.stop()
        raise RuntimeError(
            "measure_standby_outage needs "
            "sentinel.tpu.supervise.standby.enabled=true"
        )
    cli = None
    try:
        if not sup.wait_engine_up(timeout_s):
            raise RuntimeError("supervised engine never came up")
        if not sup.wait_standby_ready(timeout_s):
            raise RuntimeError("standby never reported warm")
        cli = IngestClient(sup.handles.channel(0), 0)
        deadline = time.monotonic() + timeout_s
        while True:
            v = cli.entry(resource, timeout_ms=entry_timeout_ms)
            if v.admitted and not v.degraded:
                cli.exit(resource)
                break
            if time.monotonic() > deadline:
                raise RuntimeError("engine never served a live verdict")
            time.sleep(0.02)
        killed_pid = sup.kill_engine()
        t0 = time.monotonic()
        saw_dead = False
        policy_served = 0
        while time.monotonic() - t0 < timeout_s:
            v = cli.entry(resource, timeout_ms=entry_timeout_ms)
            if v.degraded or not v.admitted:
                saw_dead = True
                policy_served += 1
            elif v.admitted:
                cli.exit(resource)
                if saw_dead:
                    outage_ms = (time.monotonic() - t0) * 1e3
                    grace = time.monotonic() + 10.0
                    while (
                        cli.counters.get("reconnects", 0) == 0
                        and time.monotonic() < grace
                    ):
                        time.sleep(0.05)
                    return {
                        "outage_ms": outage_ms,
                        "policy_served": policy_served,
                        "standby_takeovers": sup.standby_takeovers,
                        "standby_warm_boot_ms": sup.standby_warm_boot_ms,
                        "standby_attach_ms": sup.standby_attach_ms,
                        "restarts": sup.restarts,
                        "reconnects": cli.counters.get("reconnects", 0),
                        "killed_pid": killed_pid,
                    }
            time.sleep(0.002)
        raise RuntimeError(
            f"no standby takeover within {timeout_s}s "
            f"(takeovers={sup.standby_takeovers})"
        )
    finally:
        if cli is not None:
            cli.close()
        sup.stop()


def measure_handoff_outage(
    setup,
    resource: str,
    prefix: Optional[str] = None,
    timeout_s: float = 180.0,
    entry_timeout_ms: int = 30000,
) -> dict:
    """One planned config-push handoff cycle under continuous probing:
    start a supervised engine with a warm standby, probe until live,
    trigger ``EngineSupervisor.handoff()`` and keep probing through the
    drain → detach → standby-attach window. Reports the worst gap
    between consecutive live verdicts (``handoff_outage_ms`` — callers
    were HELD, not failed, for that long) and the policy-served /
    non-admitted counts, which an orderly handoff keeps at ZERO."""
    from sentinel_tpu.ipc.worker import IngestClient

    sup = EngineSupervisor(setup=setup, n_workers=1, prefix=prefix)
    if not sup.standby_enabled:
        sup.stop()
        raise RuntimeError(
            "measure_handoff_outage needs "
            "sentinel.tpu.supervise.standby.enabled=true"
        )
    cli = None
    try:
        if not sup.wait_engine_up(timeout_s):
            raise RuntimeError("supervised engine never came up")
        if not sup.wait_standby_ready(timeout_s):
            raise RuntimeError("standby never reported warm")
        cli = IngestClient(sup.handles.channel(0), 0)
        deadline = time.monotonic() + timeout_s
        while True:
            v = cli.entry(resource, timeout_ms=entry_timeout_ms)
            if v.admitted and not v.degraded:
                cli.exit(resource)
                break
            if time.monotonic() > deadline:
                raise RuntimeError("engine never served a live verdict")
            time.sleep(0.02)
        pol0 = cli.counters.get("policy_served", 0)
        old_pid = sup.engine_pid()
        result: dict = {}
        ho = threading.Thread(
            target=lambda: result.update(ok=sup.handoff(timeout_s)),
            daemon=True,
        )
        t0 = time.monotonic()
        ho.start()
        last_live = t0
        max_gap = 0.0
        not_admitted = 0
        live_after = 0
        while time.monotonic() - t0 < timeout_s:
            v = cli.entry(resource, timeout_ms=entry_timeout_ms)
            now = time.monotonic()
            if v.admitted and not v.degraded:
                cli.exit(resource)
                max_gap = max(max_gap, now - last_live)
                last_live = now
                if not ho.is_alive() and sup.engine_pid() not in (
                    None, old_pid
                ):
                    live_after += 1
                    if live_after >= 3:
                        break
            else:
                not_admitted += 1
            time.sleep(0.002)
        ho.join(timeout_s)
        if not result.get("ok"):
            raise RuntimeError(
                f"handoff never completed (handoffs={sup.handoffs})"
            )
        return {
            "handoff_outage_ms": max_gap * 1e3,
            "policy_served": cli.counters.get("policy_served", 0) - pol0,
            "not_admitted": not_admitted,
            "handoffs": sup.handoffs,
            "standby_warm_boot_ms": sup.standby_warm_boot_ms,
            "standby_attach_ms": sup.standby_attach_ms,
            "reconnects": cli.counters.get("reconnects", 0),
        }
    finally:
        if cli is not None:
            cli.close()
        sup.stop()
