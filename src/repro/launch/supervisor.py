"""Kill-drill supervisor: multi-process fault tolerance (DESIGN.md §14).

``Supervisor`` runs the jitted ``VortexStepper`` across real OS processes
— one subprocess per rank — and survives a killed or hung rank:

  * every rank advances in LOCK-STEP through the epoch barrier of
    ``parallel/resilience.py`` (the per-step cross-process collective) and
    publishes a heartbeat whose deadline is derived from the Eq 13-15 cost
    model's predicted step time (robust_wall-filtered), so a hang is
    detected in bounded time instead of blocking forever;
  * on detection (a rank's process exits, or its heartbeat goes stale past
    its own published deadline) the survivors agree on the new world size
    via the epoch-numbered view protocol, the supervisor tears down the
    dead mesh (SIGKILL on stragglers — a SIGSTOPped rank included), and
    respawns the survivors at generation g+1, each restoring
    ``VortexStepper.from_checkpoint`` onto the shrunken mesh (the elastic
    restore path is device-count independent, so the post-shrink
    trajectory is bit-identical to a clean run at the smaller world);
  * the :class:`~repro.parallel.resilience.RestartPolicy` bounds the loop:
    max restarts, exponential backoff, quarantine-then-rejoin for flapping
    ranks, and a degraded-mode floor below which a typed
    :class:`~repro.parallel.resilience.MeshFaultError` carries the
    structured fault history out.

Process model (honest scope): each rank process forces
``--xla_force_host_platform_device_count=<world>`` and redundantly
executes the world-sized SPMD program on its own host devices — exactly
the program every controller of a real multi-controller deployment would
run — while the cross-process coupling (the part a process fault actually
breaks) is the per-step epoch barrier + heartbeat protocol.  Workers can
additionally bring up the REAL jax distributed runtime
(``distributed=True`` -> ``jax.distributed.initialize`` multi-controller
on host CPU; the init barrier and coordinator service are then genuinely
cross-process), but the device program stays rank-local; wiring the
collectives themselves over ICI/NCCL is the recorded ROADMAP remainder.
Drill faults are declared in the same ``FaultSpec`` vocabulary as PR 6's
data faults: ``proc_kill`` / ``proc_hang`` sites tell the supervisor to
SIGKILL / SIGSTOP rank k mid-step n.

CLI:
  python -m repro.launch.supervisor --world 4 --target-step 6 \
      --coord-dir /tmp/drill --kill 2:4      # SIGKILL rank 2 mid-step 4
(``--worker CFG.json`` is the internal rank entry point.)
"""
from __future__ import annotations

import dataclasses
import json
import os
import signal
import subprocess
import sys
import time
from typing import Optional, Sequence

from ..configs.backend import compile_cache_dir
from ..parallel import resilience as rz

_SRC_DIR = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


# ---------------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class SupervisorConfig:
    world: int
    target_step: int
    coord_dir: str
    checkpoint_dir: Optional[str] = None    # default: <coord_dir>/ckpt
    # scenario (gen-0 build; later generations restore from checkpoint)
    n_side: int = 20
    p: int = 4
    dt: float = 0.004
    target_per_box: float = 8.0
    plan_method: str = "model"              # deterministic across ranks:
    use_kernels: bool = False               # measured-feedback replanning
    checkpoint_every: int = 2               # would diverge rank states
    checkpoint_keep: int = 8
    distributed: bool = False               # jax.distributed.initialize gang
    watchdog: rz.WatchdogPolicy = dataclasses.field(
        default_factory=rz.WatchdogPolicy)
    restart: rz.RestartPolicy = dataclasses.field(
        default_factory=rz.RestartPolicy)
    max_wall: float = 1800.0                # hard supervisor wall clock
    poll_interval: float = 0.1

    def __post_init__(self):
        if self.checkpoint_dir is None:
            self.checkpoint_dir = os.path.join(self.coord_dir, "ckpt")


@dataclasses.dataclass
class SupervisorResult:
    success: bool
    final_step: int
    generations: list                       # per-generation summary dicts
    faults: list                            # ProcFaultReport per shrink
    world_history: list                     # [(generation, ranks), ...]
    result_dir: str                         # gen dir with result_<rank>.npz
    ranks: tuple                            # final generation's ranks

    def describe(self) -> dict:
        d = dataclasses.asdict(self)
        d["faults"] = [f.describe() for f in self.faults]
        return d


# ---------------------------------------------------------------------------
# the supervisor
# ---------------------------------------------------------------------------


class Supervisor:
    """Spawns rank workers, watches heartbeats/exits, executes proc-fault
    drills, and coordinates shrink + generation-stamped restart."""

    def __init__(self, config: SupervisorConfig, faults=None):
        self.cfg = config
        self.faults = faults                # FaultInjector with proc sites
        self.fault_history: dict = {}       # rank -> [generation, ...]
        self.reports: list = []
        self.generations: list = []
        self.world_history: list = []

    # -- worker process management ------------------------------------------

    def _worker_env(self, world: int) -> dict:
        env = dict(os.environ)
        env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={world}")
        env["JAX_PLATFORMS"] = "cpu"
        # shared compilation cache: every rank lowers the identical program,
        # so one rank compiles and the rest (and later generations /
        # comparison runs) hit the cache — essential at 1-core CI.  An
        # inherited cache dir wins, so a test session can share one cache
        # across drills; otherwise the checkout's fixed cache directory.
        env["JAX_COMPILATION_CACHE_DIR"] = compile_cache_dir()
        env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
        pp = env.get("PYTHONPATH", "")
        if _SRC_DIR not in pp.split(os.pathsep):
            env["PYTHONPATH"] = _SRC_DIR + (os.pathsep + pp if pp else "")
        return env

    def _spawn_generation(self, generation: int, ranks: Sequence[int],
                          restore_step: Optional[int],
                          seconds_per_work: Optional[float]) -> dict:
        gdir = rz.gen_dir(self.cfg.coord_dir, generation)
        world = len(ranks)
        coordinator = None
        if self.cfg.distributed:
            import socket
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            coordinator = f"127.0.0.1:{s.getsockname()[1]}"
            s.close()
        procs = {}
        for rank in ranks:
            cfg = {
                "rank": int(rank), "ranks": [int(r) for r in ranks],
                "generation": int(generation),
                "coord_dir": self.cfg.coord_dir,
                "checkpoint_dir": self.cfg.checkpoint_dir,
                "restore_step": restore_step,
                "target_step": self.cfg.target_step,
                "n_side": self.cfg.n_side, "p": self.cfg.p,
                "dt": self.cfg.dt,
                "target_per_box": self.cfg.target_per_box,
                "plan_method": self.cfg.plan_method,
                "use_kernels": self.cfg.use_kernels,
                "checkpoint_every": self.cfg.checkpoint_every,
                "checkpoint_keep": self.cfg.checkpoint_keep,
                "seconds_per_work": seconds_per_work,
                "coordinator": coordinator,
                "num_processes": world,
                "process_index": list(ranks).index(rank),
                "watchdog": dataclasses.asdict(self.cfg.watchdog),
            }
            cfg_path = os.path.join(gdir, f"worker_{rank}.json")
            with open(cfg_path, "w") as f:
                json.dump(cfg, f)
            log = open(os.path.join(gdir, f"worker_{rank}.log"), "w")
            procs[rank] = (subprocess.Popen(
                [sys.executable, "-m", "repro.launch.supervisor",
                 "--worker", cfg_path],
                stdout=log, stderr=subprocess.STDOUT,
                env=self._worker_env(world)), log)
        return procs

    def _teardown(self, procs: dict) -> None:
        """SIGKILL every still-running rank (kills SIGSTOPped ones too)."""
        for rank, (p, log) in procs.items():
            if p.poll() is None:
                try:
                    os.kill(p.pid, signal.SIGKILL)
                except OSError:
                    pass
            try:
                p.wait(timeout=30)
            except subprocess.TimeoutExpired:
                pass
            log.close()

    # -- drill execution (proc_kill / proc_hang FaultSpec sites) ------------

    def _proc_specs(self) -> list:
        if self.faults is None:
            return []
        return list(self.faults.proc_faults())

    def _maybe_fire_drills(self, generation, ranks, procs, fired) -> list:
        """Execute due proc-fault specs; returns [(spec, t_injected)]."""
        events = []
        for spec in self._proc_specs():
            key = (spec.site, spec.rank, spec.step)
            if key in fired or spec.rank not in ranks:
                continue
            hb = rz.read_heartbeat(self.cfg.coord_dir, generation, spec.rank)
            if hb is None:
                continue
            due = (hb["step"] >= spec.step or
                   (hb["step"] >= spec.step - 1 and hb["phase"] == "step"))
            if not due:
                continue
            p, _ = procs[spec.rank]
            sig = (signal.SIGKILL if spec.site == "proc_kill"
                   else signal.SIGSTOP)
            try:
                os.kill(p.pid, sig)
                events.append((spec, time.time()))
            except OSError:
                pass
            fired.add(key)
        return events

    # -- the generation loop ------------------------------------------------

    def run(self) -> SupervisorResult:
        cfg = self.cfg
        os.makedirs(cfg.coord_dir, exist_ok=True)
        t_run0 = time.time()
        generation, restarts = 0, 0
        ranks = tuple(range(cfg.world))
        restore_step: Optional[int] = None
        seconds_per_work: Optional[float] = None
        fired: set = set()
        pending_report: Optional[rz.ProcFaultReport] = None

        while True:
            self.world_history.append((generation, list(ranks)))
            t_spawn = time.time()
            procs = self._spawn_generation(generation, ranks, restore_step,
                                           seconds_per_work)
            watchdog = rz.Watchdog(cfg.coord_dir, generation, ranks,
                                   cfg.watchdog)
            gen_rec = {"generation": generation, "ranks": list(ranks),
                       "restore_step": restore_step, "outcome": None}
            t_inject = t_detect = t_restored = t_first = None
            injected: list = []
            dead_exits: dict = {}
            shrink_exits: set = set()
            done_ranks: set = set()

            while True:
                time.sleep(cfg.poll_interval)
                now = time.time()
                if now - t_run0 > cfg.max_wall:
                    self._teardown(procs)
                    raise rz.MeshFaultError(
                        f"supervisor wall clock exceeded "
                        f"({cfg.max_wall:.0f}s)", self.reports)

                injected += self._maybe_fire_drills(generation, ranks, procs,
                                                    fired)
                if injected and t_inject is None:
                    t_inject = injected[0][1]

                hbs = {r: rz.read_heartbeat(cfg.coord_dir, generation, r)
                       for r in ranks}
                live = {r for r in ranks if r not in done_ranks}
                if t_restored is None and all(
                        hbs[r] and hbs[r]["phase"] != "boot" for r in ranks):
                    t_restored = now
                    # close the PREVIOUS fault's restore_seconds window
                    if pending_report is not None:
                        pending_report.restore_seconds = (
                            now - t_spawn + pending_report.restore_seconds)
                base_step = restore_step if restore_step is not None else 0
                if t_first is None and any(
                        hbs[r] and hbs[r]["step"] > base_step for r in ranks):
                    t_first = now
                    if pending_report is not None and t_restored is not None:
                        pending_report.first_step_seconds = now - t_restored
                        pending_report = None

                for r in list(live):
                    p, _ = procs[r]
                    rc = p.poll()
                    if rc is None:
                        continue
                    if rc == 0:
                        done_ranks.add(r)
                    elif rc == rz.EXIT_SHRINK:
                        shrink_exits.add(r)
                        done_ranks.add(r)       # exited deliberately
                    else:
                        dead_exits[r] = rc
                        done_ranks.add(r)

                if len(done_ranks) == len(ranks) and not dead_exits \
                        and not shrink_exits:
                    gen_rec["outcome"] = "completed"
                    self.generations.append(gen_rec)
                    self._teardown(procs)
                    return SupervisorResult(
                        success=True, final_step=cfg.target_step,
                        generations=self.generations, faults=self.reports,
                        world_history=self.world_history,
                        result_dir=rz.gen_dir(cfg.coord_dir, generation),
                        ranks=ranks)

                hung = {r: over for r, over in watchdog.overdue(now).items()
                        if r not in done_ranks and r not in dead_exits}
                announcement = rz.read_fault(cfg.coord_dir, generation)
                faulted = bool(dead_exits or hung or shrink_exits
                               or announcement)
                if not faulted:
                    continue
                if t_detect is None:
                    t_detect = now
                    # tell still-waiting ranks immediately (first writer
                    # wins; rank-side detections keep their own timestamp)
                    rz.announce_fault(cfg.coord_dir, generation,
                                      sorted(set(dead_exits) | set(hung)),
                                      epoch=None, by="supervisor")
                # give survivors a bounded grace to agree + exit on their
                # own; then tear the remnant mesh down
                remaining = [r for r in ranks if r not in done_ranks
                             and procs[r][0].poll() is None]
                if remaining and now - t_detect < cfg.watchdog.teardown_grace:
                    continue
                break

            # -- coordinated shrink -----------------------------------------
            self._teardown(procs)
            announcement = rz.read_fault(cfg.coord_dir, generation)
            decision = rz.read_decision(cfg.coord_dir, generation)
            dead = sorted(set(dead_exits) | set(hung) |
                          set((announcement or {}).get("dead", [])))
            if decision is not None:
                survivors = tuple(r for r in decision["survivors"]
                                  if r not in dead)
            else:
                survivors = tuple(r for r in ranks if r not in dead)
            for r in dead:
                self.fault_history.setdefault(r, []).append(generation)
            restarts += 1
            # carry the measured seconds-per-work calibration across the
            # restart so the next generation's watchdog deadline starts
            # from the cost model instead of the compile grace
            spus = [hbs[r]["spu"] for r in ranks
                    if hbs.get(r) and hbs[r].get("spu")]
            if spus:
                seconds_per_work = sorted(spus)[len(spus) // 2]

            try:
                from ..checkpoint.manager import CheckpointManager
                restore_step = CheckpointManager(
                    cfg.checkpoint_dir, keep=cfg.checkpoint_keep).latest_step()
            except OSError:
                restore_step = None

            report = rz.ProcFaultReport(
                generation=generation,
                epoch=(decision or announcement or {}).get("epoch"),
                dead=tuple(sorted(set(dead_exits) |
                                  set((announcement or {}).get("dead", []))
                                  - set(hung))),
                hung=tuple(sorted(hung)),
                world_before=len(ranks), world_after=len(survivors),
                restore_step=restore_step,
                detected_by=(announcement or {}).get("by", "supervisor"),
                detect_seconds=(t_detect - t_inject
                                if t_inject is not None and t_detect
                                else None),
                restore_seconds=0.0,    # grown by the next gen's milestones
                reason="shrink")
            self.reports.append(report)
            pending_report = report
            gen_rec["outcome"] = "fault"
            gen_rec["fault"] = str(report)
            self.generations.append(gen_rec)

            if restarts > cfg.restart.max_restarts:
                raise rz.MeshFaultError(
                    f"max restarts exceeded ({cfg.restart.max_restarts})",
                    self.reports)
            next_ranks = cfg.restart.next_ranks(survivors, generation,
                                                self.fault_history)
            if len(next_ranks) < cfg.restart.min_world:
                raise rz.MeshFaultError(
                    f"world shrank below the degraded-mode floor "
                    f"({len(next_ranks)} < {cfg.restart.min_world})",
                    self.reports)
            time.sleep(cfg.restart.backoff(restarts))
            # account teardown+backoff into the report's restore window
            report.restore_seconds = time.time() - t_detect
            generation += 1
            ranks = next_ranks


# ---------------------------------------------------------------------------
# the rank worker
# ---------------------------------------------------------------------------


def _init_distributed(cfg: dict) -> None:
    """Bring up the real jax multi-controller runtime (host CPU gang)."""
    import jax
    jax.distributed.initialize(coordinator_address=cfg["coordinator"],
                               num_processes=cfg["num_processes"],
                               process_id=cfg["process_index"])


def worker_main(cfg_path: str) -> int:
    with open(cfg_path) as f:
        cfg = json.load(f)
    rank, gen = cfg["rank"], cfg["generation"]
    ranks = tuple(cfg["ranks"])
    world = len(ranks)
    policy = rz.WatchdogPolicy(**cfg["watchdog"])
    coord = cfg["coord_dir"]
    hb = rz.Heartbeat(coord, gen, rank)
    hb.beat(step=cfg["restore_step"] or 0, phase="boot",
            deadline=policy.compile_grace)

    if cfg.get("distributed") or cfg.get("coordinator"):
        _init_distributed(cfg)
    import numpy as np
    import jax  # noqa: F401  (configured via env by the supervisor)
    from ..core.stepper import VortexStepper
    from ..core.vortex import lamb_oseen_particles
    from .mesh import make_world_mesh

    mesh = make_world_mesh(world)
    is_writer = rank == min(ranks)
    ck_dir, ck_every = cfg["checkpoint_dir"], cfg["checkpoint_every"]
    if cfg["restore_step"] is not None:
        st = VortexStepper.from_checkpoint(
            ck_dir, mesh=mesh, step=cfg["restore_step"],
            plan_method=cfg["plan_method"], use_kernels=cfg["use_kernels"],
            checkpoint_every=ck_every if is_writer else 0,
            checkpoint_keep=cfg["checkpoint_keep"])
    else:
        pos, gamma, sigma = lamb_oseen_particles(cfg["n_side"])
        st = VortexStepper(
            pos, gamma, sigma, p=cfg["p"], dt=cfg["dt"], mesh=mesh,
            plan_method=cfg["plan_method"], use_kernels=cfg["use_kernels"],
            target_per_box=cfg["target_per_box"],
            checkpoint_dir=ck_dir if is_writer else None,
            checkpoint_every=ck_every,
            checkpoint_keep=cfg["checkpoint_keep"])
        if is_writer:
            st.save_checkpoint()    # step 0: a shrink always has a restore
            st._ckpt.wait()         # point, even before the first cadence
    hb.beat(step=st.step_count, phase="restored",
            deadline=policy.compile_grace)

    barrier = rz.EpochBarrier(coord, gen, rank, ranks,
                              poll_interval=policy.poll_interval)
    watchdog = rz.Watchdog(coord, gen, ranks, policy)

    state = {"spu": cfg.get("seconds_per_work")}

    def detect_and_exit(dead, epoch):
        # Agreement can take a while (everyone converges on the survivor
        # view) — publish a deadline that covers it so the supervisor's
        # watchdog never mistakes an agreeing rank for a hung one.
        hb.beat(step=st.step_count, phase="agree",
                deadline=policy.agree_timeout + policy.slack,
                spu=state["spu"])
        ann = rz.announce_fault(coord, gen, dead, epoch, by=rank)
        dead = sorted(set(dead) | set(ann["dead"]))
        epoch = ann["epoch"] if ann.get("epoch") is not None else epoch
        if rank in dead:
            # The standing announcement names THIS rank (a watchdog race:
            # e.g. the supervisor flagged us while we blocked on a dead
            # peer).  Don't fight the vote — the survivors' decision
            # excludes us, so step aside and let the rebuild proceed.
            hb.beat(step=st.step_count, phase="evicted",
                    deadline=policy.compile_grace, spu=state["spu"])
            raise SystemExit(rz.EXIT_SHRINK)
        proposed = [r for r in ranks if r not in dead]
        agreed = rz.agree_view(coord, gen, rank, proposed, epoch,
                               timeout=policy.agree_timeout,
                               poll_interval=policy.poll_interval)
        assert rank in agreed
        if is_writer and st._ckpt is not None:
            st._ckpt.wait()         # never strand an in-flight snapshot
        hb.beat(step=st.step_count, phase="shrink",
                deadline=policy.compile_grace, spu=state["spu"])
        raise SystemExit(rz.EXIT_SHRINK)

    compiled = False
    modeled_work = st.modeled_step_work()
    while st.step_count < cfg["target_step"]:
        predicted = st.predicted_step_seconds()
        if predicted is None:
            predicted = rz.predicted_from_calibration(state["spu"],
                                                      modeled_work)
        deadline = rz.step_deadline(policy, predicted, compiled)
        hb.beat(step=st.step_count, phase="step", deadline=deadline,
                spu=state["spu"])
        epoch, rounds = st.step_count, 0
        # Beat on every barrier poll: a rank legitimately waiting out its
        # peer's deadline must keep proving liveness, or its own heartbeat
        # ages past the published deadline and the watchdog (supervisor's
        # or a peer's) flags the WAITER as hung alongside the real fault.
        refresh = lambda: hb.beat(step=st.step_count, phase="step",
                                  deadline=deadline, spu=state["spu"])
        while True:                     # the per-step collective
            try:
                barrier.wait(epoch, timeout=deadline, on_poll=refresh)
                break
            except rz.FaultAnnounced as e:
                detect_and_exit(e.dead, e.epoch if e.epoch is not None
                                else epoch)
            except rz.BarrierTimeout as e:
                stale = [r for r in watchdog.overdue()
                         if r != rank and r in e.missing]
                if stale:
                    detect_and_exit(stale, epoch)
                rounds += 1             # laggards still fresh: wait more,
                if rounds >= policy.max_barrier_rounds:     # but bounded
                    detect_and_exit(list(e.missing), epoch)
        rec = st.step()
        compiled = not (rec.replanned or rec.releveled)
        if not compiled:
            modeled_work = st.modeled_step_work()
        sample = st.predicted_step_seconds()
        if sample is not None and modeled_work > 0:
            state["spu"] = sample / modeled_work
    hb.beat(step=st.step_count, phase="done", deadline=policy.compile_grace,
            spu=state["spu"])
    out = os.path.join(rz.gen_dir(coord, gen), f"result_{rank}.npz")
    np.savez(out, z=np.asarray(st.tree.z), q=np.asarray(st.tree.q),
             mask=np.asarray(st.tree.mask), step=st.step_count)
    if is_writer and st._ckpt is not None:
        st._ckpt.wait()
    return 0


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def _parse_drills(kills, hangs):
    from ..core.faults import FaultInjector, FaultSpec
    specs = []
    for site, items in (("proc_kill", kills), ("proc_hang", hangs)):
        for item in items or ():
            r, s = item.split(":")
            specs.append(FaultSpec(site=site, step=int(s), device=int(r)))
    return FaultInjector(*specs) if specs else None


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        prog="python -m repro.launch.supervisor",
        description="multi-process kill-drill supervisor (DESIGN.md §14)")
    ap.add_argument("--worker", metavar="CFG", default=None,
                    help=argparse.SUPPRESS)   # internal rank entry point
    ap.add_argument("--world", type=int, default=4)
    ap.add_argument("--target-step", type=int, default=6)
    ap.add_argument("--coord-dir", default="/tmp/fmm-drill")
    ap.add_argument("--n-side", type=int, default=20)
    ap.add_argument("--p", type=int, default=4)
    ap.add_argument("--dt", type=float, default=0.004)
    ap.add_argument("--checkpoint-every", type=int, default=2)
    ap.add_argument("--kill", action="append", metavar="RANK:STEP",
                    help="SIGKILL rank mid-step (repeatable)")
    ap.add_argument("--hang", action="append", metavar="RANK:STEP",
                    help="SIGSTOP rank mid-step (repeatable)")
    ap.add_argument("--min-world", type=int, default=1)
    ap.add_argument("--max-restarts", type=int, default=3)
    ap.add_argument("--max-wall", type=float, default=1800.0)
    ap.add_argument("--distributed", action="store_true",
                    help="bring up jax.distributed multi-controller")
    args = ap.parse_args(argv)

    if args.worker:
        return worker_main(args.worker)

    cfg = SupervisorConfig(
        world=args.world, target_step=args.target_step,
        coord_dir=args.coord_dir, n_side=args.n_side, p=args.p, dt=args.dt,
        checkpoint_every=args.checkpoint_every, distributed=args.distributed,
        restart=rz.RestartPolicy(max_restarts=args.max_restarts,
                                 min_world=args.min_world),
        max_wall=args.max_wall)
    sup = Supervisor(cfg, faults=_parse_drills(args.kill, args.hang))
    result = sup.run()
    print(json.dumps(result.describe(), indent=2, default=str))
    return 0 if result.success else 1


if __name__ == "__main__":
    sys.exit(main())
