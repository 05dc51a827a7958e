"""Cross-process fault tolerance (DESIGN.md §14): watchdog deadlines,
heartbeat staleness, epoch-barrier agreement, restart policy, and the
kill/hang drills.

The drills spawn REAL OS processes via ``launch/supervisor.py`` — rank
workers running the jitted ``VortexStepper`` in lock-step — SIGKILL (or
SIGSTOP) one mid-step, and assert the run completes on the survivors with
the post-restore trajectory bit-identical to a clean shrunken-world run
resumed from the same checkpoint.  One jax compilation cache is shared
across every subprocess of the module so each distinct world size
compiles once per session.
"""
import json
import os
import signal
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

from repro.parallel import resilience as rz
from repro.core.faults import FaultInjector, FaultSpec, PROC_SITES, SITES
from repro.launch.supervisor import Supervisor, SupervisorConfig

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

# one drill scenario for the whole module: the 3-rank generation of the
# kill drill, the hang drill's gen 0, and the clean comparison run all
# lower the identical program, so the shared cache pays each world size's
# compile once
N_SIDE, P, DT = 20, 4, 0.004


@pytest.fixture(scope="module")
def jax_cache(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("jaxcache"))
    old = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = d
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    yield d
    if old is None:
        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    else:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = old


# ---------------------------------------------------------------------------
# deadline computation (satellite: tight but not flappy)
# ---------------------------------------------------------------------------


def test_step_deadline_units():
    pol = rz.WatchdogPolicy(margin=3.0, slack=2.0, min_deadline=1.0,
                            compile_grace=300.0)
    # no estimate yet -> compile grace
    assert rz.step_deadline(pol, None) == 300.0
    # steady state: margin * predicted + slack
    assert rz.step_deadline(pol, 0.5) == pytest.approx(3.5)
    # floored (slack=0 so the floor binds)
    assert rz.step_deadline(
        rz.WatchdogPolicy(margin=3.0, slack=0.0, min_deadline=1.0),
        1e-6) == 1.0
    # a step known to retrace gets the grace window even with an estimate
    assert rz.step_deadline(pol, 0.5, compiled=False) == 300.0
    # Eq 13-15 calibration path
    assert rz.predicted_from_calibration(2e-6, 1e5) == pytest.approx(0.2)
    assert rz.predicted_from_calibration(None, 1e5) is None
    assert rz.predicted_from_calibration(2e-6, None) is None
    assert rz.predicted_from_calibration(0.0, 1e5) is None


def test_watchdog_deadline_no_false_positives_20_steps(tmp_path, jax_cache):
    """Cost-model-derived deadlines across 20 clean steps at 4 ranks
    (4 forced host devices): every step finishes inside the deadline
    computed BEFORE it ran (no false positive would ever trip the
    barrier), and post-warmup deadlines are tight (far below the compile
    grace window)."""
    body = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import json
        from repro.core.stepper import VortexStepper
        from repro.core.vortex import lamb_oseen_particles
        from repro.launch.mesh import make_world_mesh
        from repro.parallel import resilience as rz

        pol = rz.WatchdogPolicy(margin=3.0, slack=0.5, min_deadline=0.05,
                                compile_grace=900.0)
        pos, gamma, sigma = lamb_oseen_particles({N_SIDE})
        st = VortexStepper(pos, gamma, sigma, p={P}, dt={DT},
                           mesh=make_world_mesh(4), plan_method="model")
        rows, compiled = [], False
        for _ in range(20):
            deadline = rz.step_deadline(pol, st.predicted_step_seconds(),
                                        compiled)
            rec = st.step()
            compiled = not (rec.replanned or rec.releveled)
            rows.append((deadline, rec.seconds))
        print("ROWS " + json.dumps(rows))
    """)
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", body], capture_output=True,
                       text=True, timeout=900, env=env)
    assert r.returncode == 0, f"\nSTDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    rows = json.loads(r.stdout.split("ROWS ", 1)[1].splitlines()[0])
    assert len(rows) == 20
    for i, (deadline, seconds) in enumerate(rows):
        assert seconds < deadline, \
            f"step {i + 1}: false positive ({seconds:.3f}s > {deadline:.3f}s)"
    # tight after warmup: the last deadlines come from measured steady
    # state, nowhere near the compile grace fallback
    tail = [d for d, _ in rows[5:]]
    assert max(tail) < 900.0 / 4, f"deadlines never tightened: {tail}"


# ---------------------------------------------------------------------------
# heartbeat staleness (satellite: SIGSTOPped peer)
# ---------------------------------------------------------------------------


def test_heartbeat_staleness_sigstop_peer(tmp_path):
    """A SIGSTOPped beater (pure stdlib subprocess — no jax) goes overdue
    against its OWN published deadline within bounded time; a beating peer
    never does."""
    beater = textwrap.dedent(f"""
        import sys, time
        sys.path.insert(0, {SRC!r})
        from repro.parallel import resilience as rz
        hb = rz.Heartbeat({str(tmp_path)!r}, 0, 1)
        while True:
            hb.beat(step=3, phase="step", deadline=0.5)
            time.sleep(0.05)
    """)
    p = subprocess.Popen([sys.executable, "-c", beater])
    pol = rz.WatchdogPolicy(compile_grace=30.0)
    wd = rz.Watchdog(str(tmp_path), 0, ranks=(1,), policy=pol)
    try:
        deadline = time.time() + 10
        while rz.read_heartbeat(str(tmp_path), 0, 1) is None:
            assert time.time() < deadline, "beater never started"
            time.sleep(0.02)
        time.sleep(0.3)
        assert wd.overdue() == {}          # beating -> fresh
        assert wd.fresh() == (1,)
        os.kill(p.pid, signal.SIGSTOP)     # hung, not dead
        t0 = time.time()
        while not wd.overdue():
            assert time.time() - t0 < 5.0, \
                "stopped beater never went overdue"
            time.sleep(0.05)
        over = wd.overdue()
        assert 1 in over and over[1] > 0.0
        assert wd.fresh() == ()
        # hb file still shows the stopped rank's final published deadline
        assert rz.read_heartbeat(str(tmp_path), 0, 1)["deadline"] == 0.5
    finally:
        os.kill(p.pid, signal.SIGCONT)
        p.kill()
        p.wait()


def test_watchdog_never_beat_rank(tmp_path):
    pol = rz.WatchdogPolicy(compile_grace=0.2)
    wd = rz.Watchdog(str(tmp_path), 0, ranks=(0,), policy=pol)
    assert wd.overdue() == {}              # inside the boot grace
    time.sleep(0.3)
    assert 0 in wd.overdue()               # grace expired, no beat ever


# ---------------------------------------------------------------------------
# epoch barrier + membership agreement (satellite: concurrent detection)
# ---------------------------------------------------------------------------


def test_epoch_barrier_passes_and_times_out(tmp_path):
    d = str(tmp_path)
    b0 = rz.EpochBarrier(d, 0, 0, (0, 1), poll_interval=0.01)
    b1 = rz.EpochBarrier(d, 0, 1, (0, 1), poll_interval=0.01)
    t = threading.Thread(target=lambda: b1.wait(0, timeout=5.0))
    t.start()
    b0.wait(0, timeout=5.0)
    t.join(timeout=5.0)
    assert not t.is_alive()
    # rank 1 never reaches epoch 1 -> bounded timeout naming the laggard;
    # on_poll fires every poll so a blocked waiter can keep its heartbeat
    # fresh (a stale WAITER would be indistinguishable from a hung rank)
    beats = []
    with pytest.raises(rz.BarrierTimeout) as ei:
        b0.wait(1, timeout=0.3, on_poll=lambda: beats.append(time.time()))
    assert ei.value.missing == (1,)
    assert ei.value.epoch == 1
    assert len(beats) >= 5


def test_barrier_aborts_on_fault_announcement(tmp_path):
    """A waiting rank aborts IMMEDIATELY when a fault announcement lands —
    detection is not serialized behind the full timeout."""
    d = str(tmp_path)
    b0 = rz.EpochBarrier(d, 0, 0, (0, 1), poll_interval=0.01)
    caught = {}

    def waiter():
        try:
            b0.wait(0, timeout=60.0)
        except rz.FaultAnnounced as e:
            caught["dead"] = e.dead
            caught["t"] = time.time()

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.2)
    t0 = time.time()
    rz.announce_fault(d, 0, [1], epoch=0, by="supervisor")
    t.join(timeout=5.0)
    assert not t.is_alive()
    assert caught["dead"] == (1,)
    assert caught["t"] - t0 < 2.0          # nowhere near the 60s timeout


def test_concurrent_detection_single_decision(tmp_path):
    """Two ranks detect the same death concurrently: identical proposals,
    both announce (first writer wins), both agree on the same survivor
    view, and exactly ONE decision is published."""
    d = str(tmp_path)
    results, anns = {}, {}

    def detect(rank):
        anns[rank] = rz.announce_fault(d, 0, [2], epoch=7, by=rank)
        results[rank] = rz.agree_view(d, 0, rank, [0, 1], 7, timeout=5.0)

    ts = [threading.Thread(target=detect, args=(r,)) for r in (0, 1)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=30.0)
        assert not t.is_alive()
    assert results[0] == results[1] == (0, 1)
    # the announcement is idempotent: both detectors saw one winner
    assert anns[0] == anns[1]
    assert anns[0]["dead"] == [2]
    decisions = [n for n in os.listdir(os.path.join(d, "gen_0"))
                 if n.startswith("decision_") and n.endswith(".json")]
    assert decisions == ["decision_7.json"]
    assert rz.read_decision(d, 0)["survivors"] == [0, 1]


def test_a_detector_that_loses_the_lock_gets_the_winners_record(tmp_path):
    """The race behind concurrent detection, made to happen: the winner
    holds ``fault.json``'s lock but publishes its record 0.2 s later; the
    loser waits for that record instead of returning its own."""
    d = str(tmp_path)
    path = os.path.join(rz.gen_dir(d, 0), "fault.json")
    open(path + ".lock", "w").close()
    winner = {"dead": [2], "epoch": 7, "by": 0, "t": 0.0}
    t = threading.Timer(0.2, rz._write_atomic,
                        args=(path, json.dumps(winner)))
    t.start()
    try:
        got = rz.announce_fault(d, 0, [2], epoch=7, by=1)
    finally:
        t.join()
    assert got == winner


def test_divergent_views_converge_by_intersection(tmp_path):
    """One detector still believes a doubly-dead rank is alive; the views
    are intersected and re-voted at epoch+1 until identical."""
    d = str(tmp_path)
    results = {}

    def vote(rank, proposed):
        results[rank] = rz.agree_view(d, 0, rank, proposed, 3,
                                      timeout=1.0, max_rounds=4)

    ts = [threading.Thread(target=vote, args=(0, [0, 1])),
          threading.Thread(target=vote, args=(1, [0, 1, 3]))]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=30.0)
        assert not t.is_alive()
    # rank 3 never votes: dropped on timeout / intersection; both converge
    assert results[0] == results[1] == (0, 1)


def test_agreement_rejects_selfless_proposal(tmp_path):
    with pytest.raises(rz.AgreementError):
        rz.agree_view(str(tmp_path), 0, 2, [0, 1], 0, timeout=0.2)


# ---------------------------------------------------------------------------
# restart policy
# ---------------------------------------------------------------------------


def test_restart_policy_backoff_and_floor():
    pol = rz.RestartPolicy(max_restarts=3, backoff_base=0.5,
                           backoff_max=4.0, min_world=2)
    assert pol.backoff(0) == 0.0
    assert [pol.backoff(n) for n in (1, 2, 3, 4, 5)] == \
        [0.5, 1.0, 2.0, 4.0, 4.0]


def test_restart_policy_quarantine_and_rejoin():
    pol = rz.RestartPolicy(rejoin_after=2, flap_limit=2)
    hist = {2: [0]}
    # quarantine not yet expired (faulted in gen 0, now entering gen 1)
    assert pol.next_ranks([0, 1, 3], 0, hist) == (0, 1, 3)
    # expired after rejoin_after generations -> rank 2 rejoins
    assert pol.next_ranks([0, 1, 3], 2, hist) == (0, 1, 2, 3)
    # a flapping rank (faulted flap_limit times) never rejoins
    assert pol.next_ranks([0, 1, 3], 9, {2: [0, 5]}) == (0, 1, 3)
    # rejoin disabled by default
    assert rz.RestartPolicy().next_ranks([0, 1], 9, hist) == (0, 1)


def test_mesh_fault_error_carries_reports():
    rep = rz.ProcFaultReport(generation=1, epoch=4, dead=(2,), hung=(),
                             world_before=4, world_after=3, restore_step=2,
                             detected_by="supervisor", detect_seconds=0.4)
    err = rz.MeshFaultError("max restarts exceeded", [rep])
    assert err.faults == (rep,)
    assert "max restarts exceeded" in str(err)
    assert "dead=[2]" in str(err)
    assert rep.describe()["world_after"] == 3


# ---------------------------------------------------------------------------
# FaultSpec promotion to process granularity
# ---------------------------------------------------------------------------


def test_proc_fault_sites():
    assert set(PROC_SITES) <= set(SITES)
    kill = FaultSpec(site="proc_kill", step=4, device=2)
    hang = FaultSpec(site="proc_hang", step=3, device=1, sticky=True)
    assert kill.rank == 2 and hang.rank == 1
    inj = FaultInjector(kill, hang,
                        FaultSpec(site="teleport", step=4),
                        FaultSpec(site="time_inflate", step=4))
    assert inj.proc_faults() == (kill, hang)
    # proc (and host) sites NEVER enter the jitted step's static tuple
    active = inj.active(4)
    assert all(f.site not in PROC_SITES + ("time_inflate",) for f in active)
    assert [f.site for f in active] == ["teleport"]
    with pytest.raises(ValueError):
        FaultSpec(site="proc_reboot", step=1)


# ---------------------------------------------------------------------------
# the drills (tentpole acceptance)
# ---------------------------------------------------------------------------


def _drill_config(tmp_path, world, target, min_world):
    return SupervisorConfig(
        world=world, target_step=target, coord_dir=str(tmp_path),
        n_side=N_SIDE, p=P, dt=DT, checkpoint_every=2, checkpoint_keep=8,
        watchdog=rz.WatchdogPolicy(compile_grace=900.0, teardown_grace=30.0,
                                   agree_timeout=120.0),
        restart=rz.RestartPolicy(min_world=min_world, backoff_base=0.1),
        max_wall=3000.0)


def _load_result(path):
    with np.load(path) as z:
        return {k: z[k] for k in z.files}


def _clean_shrunken_run(ckpt_dir, world, restore_step, target, out_path,
                        env):
    """Reference trajectory: ONE fresh process, ``world`` forced devices,
    from_checkpoint at the drill's restore step, stepped to the target."""
    body = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count={world}"
        import numpy as np
        from repro.core.stepper import VortexStepper
        from repro.launch.mesh import make_world_mesh

        st = VortexStepper.from_checkpoint(
            {ckpt_dir!r}, mesh=make_world_mesh({world}),
            step={restore_step}, plan_method="model", checkpoint_every=0)
        while st.step_count < {target}:
            st.step()
        np.savez({out_path!r}, z=np.asarray(st.tree.z),
                 q=np.asarray(st.tree.q), mask=np.asarray(st.tree.mask))
        print("clean ok")
    """)
    r = subprocess.run([sys.executable, "-c", body], capture_output=True,
                       text=True, timeout=1200, env=env)
    assert r.returncode == 0, f"\nSTDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"


def _drill_env():
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    return env


def _worker_logs(coord_dir):
    out = []
    for root, _, names in os.walk(coord_dir):
        for n in sorted(names):
            if n.endswith(".log"):
                with open(os.path.join(root, n), errors="replace") as f:
                    out.append(f"--- {os.path.join(root, n)}\n" + f.read())
    return "\n".join(out)


def test_kill_drill_4_ranks_sigkill_completes_on_3(tmp_path, jax_cache,
                                                   monkeypatch):
    """THE acceptance drill: 4 ranks, rank 2 SIGKILLed mid-step 4; the run
    completes on 3 survivors and the post-restore trajectory is
    bit-identical to a clean 3-rank run resumed from the same
    checkpoint."""
    for k, v in _drill_env().items():
        monkeypatch.setenv(k, v)
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    cfg = _drill_config(tmp_path, world=4, target=6, min_world=2)
    sup = Supervisor(cfg, faults=FaultInjector(
        FaultSpec(site="proc_kill", step=4, device=2)))
    try:
        result = sup.run()
    except rz.MeshFaultError as e:
        pytest.fail(f"drill did not survive: {e}\n"
                    f"{_worker_logs(str(tmp_path))}")
    assert result.success and result.final_step == 6

    # exactly one shrink: 4 -> 3 with rank 2 gone
    assert len(result.faults) == 1
    rep = result.faults[0]
    assert 2 in rep.dead and rep.hung == ()
    assert (rep.world_before, rep.world_after) == (4, 3)
    assert rep.restore_step is not None
    assert result.ranks == (0, 1, 3)
    # MTTR pieces are finite (the bench row publishes these)
    assert rep.detect_seconds is not None and rep.detect_seconds < 120.0
    assert rep.restore_seconds is not None and rep.restore_seconds > 0.0

    # every survivor finished with the SAME state...
    outs = [_load_result(os.path.join(result.result_dir, f"result_{r}.npz"))
            for r in result.ranks]
    for o in outs[1:]:
        for k in ("z", "q", "mask"):
            np.testing.assert_array_equal(o[k], outs[0][k])

    # ...bit-identical to a clean 3-rank run from the same checkpoint
    clean_path = str(tmp_path / "clean3.npz")
    _clean_shrunken_run(cfg.checkpoint_dir, 3, rep.restore_step, 6,
                        clean_path, _drill_env())
    clean = _load_result(clean_path)
    for k in ("z", "q", "mask"):
        np.testing.assert_array_equal(outs[0][k], clean[k],
                                      err_msg=f"{k} diverged from the "
                                      f"clean shrunken-world run")


def test_hang_drill_sigstop_detected_within_deadline(tmp_path, jax_cache,
                                                     monkeypatch):
    """Hung-not-dead: rank 1 of 3 SIGSTOPped mid-step.  The watchdog (not
    CI's killer) must detect the stale heartbeat in bounded time, the
    survivors shrink to 2, and the run completes."""
    for k, v in _drill_env().items():
        monkeypatch.setenv(k, v)
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    cfg = _drill_config(tmp_path, world=3, target=5, min_world=1)
    sup = Supervisor(cfg, faults=FaultInjector(
        FaultSpec(site="proc_hang", step=3, device=1)))
    try:
        result = sup.run()
    except rz.MeshFaultError as e:
        pytest.fail(f"hang drill did not survive: {e}\n"
                    f"{_worker_logs(str(tmp_path))}")
    assert result.success and result.final_step == 5
    assert len(result.faults) == 1
    rep = result.faults[0]
    assert 1 in (rep.hung + rep.dead)      # stale heartbeat, not an exit
    assert rep.world_after == 2
    assert result.ranks == (0, 2)
    # bounded detection: stale-heartbeat deadlines, not the compile grace
    # window and certainly not the CI job timeout
    assert rep.detect_seconds is not None and rep.detect_seconds < 120.0
