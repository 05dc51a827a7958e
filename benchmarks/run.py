"""Benchmark harness: one entry per paper table/figure + kernel benches.

Prints ``name,us_per_call,derived`` CSV rows (the scaffold contract).
``derived`` carries the figure-specific metric (efficiency, LB, GB/s, ...).
Run: PYTHONPATH=src python -m benchmarks.run [--quick] [--json PATH]

``--json PATH`` additionally writes the rows as a JSON list of
``{"name", "us_per_call", "derived"}`` objects — the machine-readable
baseline the perf acceptance criteria diff against (BENCH_fmm.json).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def _cpu_child_env() -> dict:
    """Environment of a forced-host-device child: the repo's ``src`` on
    its path and JAX held to the CPU, so it never contends for a chip
    that this (parent) process may hold."""
    env = dict(os.environ)
    src_dir = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                           "src"))
    old_pp = env.get("PYTHONPATH", "")
    env["PYTHONPATH"] = src_dir + (os.pathsep + old_pp if old_pp else "")
    env["JAX_PLATFORMS"] = "cpu"
    return env


def _time(fn, reps=3, warmup=1):
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return (time.perf_counter() - t0) / reps * 1e6  # us


def bench_fig6_stage_timings(rows, quick=False):
    """Paper Fig 6: per-stage FMM timings (measured, serial, CPU)."""
    import jax
    from repro.core import expansions as ex
    from repro.core.fmm import fmm_velocity, near_field, upward_sweep
    from repro.core.quadtree import build_tree

    n_particles, level, p = (20_000, 5, 12) if quick else (100_000, 6, 17)
    rng = np.random.default_rng(0)
    pos = rng.uniform(0.01, 0.99, (n_particles, 2))
    tree, _ = build_tree(pos, rng.normal(size=n_particles), level, 0.02)

    total = _time(lambda: jax.block_until_ready(fmm_velocity(tree, p)))
    rows.append(("fig6_total_fmm", total, f"N={n_particles}_L={level}_p={p}"))

    up = jax.jit(lambda t: upward_sweep(t, p)[0], static_argnames=())
    rows.append(("fig6_upward_sweep", _time(lambda: jax.block_until_ready(up(tree))),
                 "P2M+M2M"))
    me = upward_sweep(tree, p)
    m2l = jax.jit(lambda g: ex.m2l_reference(g, level, p))
    m2l_t = _time(lambda: jax.block_until_ready(m2l(me[level])))
    rows.append(("fig6_m2l_leaf_level", m2l_t, "M2L_parity_folded"))
    # same-op comparison: the pre-folding 40-offset masked formulation
    m2l40 = jax.jit(lambda g: ex.m2l_masked40(g, level, p))
    m2l40_t = _time(lambda: jax.block_until_ready(m2l40(me[level])))
    rows.append(("fig6_m2l_leaf_level_masked40", m2l40_t,
                 f"folded_speedup={m2l40_t / max(m2l_t, 1e-9):.2f}x"))
    nearf = jax.jit(near_field)
    rows.append(("fig6_p2p_near_field",
                 _time(lambda: jax.block_until_ready(nearf(tree))), "P2P"))


def bench_fig7_9_scaling(rows, quick=False):
    """Paper Figs 7-9: speedup / efficiency / load balance (modeled)."""
    from benchmarks.fmm_scaling import scaling_table
    level = 8 if quick else 10
    t = scaling_table(level=level, cut=4)
    for r in t:
        rows.append((f"fig7_speedup_P{r['P']}", 0.0, f"{r['S_model']:.2f}"))
        rows.append((f"fig8_efficiency_P{r['P']}", 0.0, f"{r['E_model']:.3f}"))
        rows.append((f"fig9_loadbalance_P{r['P']}", 0.0,
                     f"model={r['LB_model']:.3f}_uniform={r['LB_uniform']:.3f}"))


def bench_table12_memory(rows, quick=False):
    """Paper §5.3 Tables 1-2 + the 64M-particle headline (<1.01 GB/proc)."""
    from repro.core import cost_model as cm
    params = cm.ModelParams(level=10, cut=4, p=17, slots=1)
    mem = cm.memory_serial(params, 765_625)
    rows.append(("table1_serial_total_MB", 0.0, f"{sum(mem.values())/1e6:.1f}"))
    par = cm.memory_parallel(params, 64, 256, 64)
    rows.append(("table2_parallel_total_MB", 0.0, f"{sum(par.values())/1e6:.1f}"))
    # 64M particles / 64 procs headline (paper: 115.8 s, < 1.01 GB/proc)
    params64 = cm.ModelParams(level=12, cut=5, p=17, slots=4)
    per_proc = (sum(cm.memory_serial(params64, 64_000_000).values()) / 64 +
                sum(cm.memory_parallel(params64, 64, 1024, 128).values()))
    rows.append(("headline_64M_per_proc_paperTable_GB", 0.0, f"{per_proc/1e9:.2f}"))
    # our dense implementation stores NO interaction lists/values (generated
    # from the 40 static offsets — the paper's own 'future improvement'):
    L, p, s = 12, 17, 4
    nleaf = 4 ** L
    lam = cm.total_boxes(L)
    ours = (nleaf * s * (8 + 8 + 1 + 8)      # z, q, mask, W
            + lam * p * 8 * 2) / 64          # ME + LE grids (complex64)
    rows.append(("headline_64M_per_proc_ours_GB", 0.0, f"{ours/1e9:.2f}"))


def bench_kernels(rows, quick=False):
    """Pallas kernels vs jnp reference, same op on both sides, through the
    ``kernels/ops`` wrappers (off-TPU the kernels run in the Pallas
    interpreter, so their wall time is a validation-mode number and the
    row name says ``_interpret``; 'derived' reports the oracle error)."""
    import jax
    import jax.numpy as jnp
    from repro.core import expansions as ex
    from repro.kernels import ops as kops
    from repro.kernels import ref
    from repro.kernels.p2p import P2P_HALO

    mode = "_interpret" if kops._interpret() else ""
    rng = np.random.default_rng(0)
    ny = nx = 8 if quick else 16
    s = 8
    z = jnp.asarray(rng.uniform(size=(ny, nx, s)) + 1j * rng.uniform(size=(ny, nx, s)),
                    jnp.complex64)
    q = jnp.asarray(rng.normal(size=(ny, nx, s)) + 0j, jnp.complex64)
    mask = jnp.ones((ny, nx, s), bool)
    halo = ((P2P_HALO, P2P_HALO), (P2P_HALO, P2P_HALO), (0, 0))

    def p2p_kernel():
        return kops.p2p_apply_slab(jnp.pad(z, halo), jnp.pad(q, halo),
                                   jnp.pad(mask, halo), 0.05)

    expect = np.asarray(ref.p2p_ref(z, q, mask, 0.05))
    p2p_jit = jax.jit(lambda a, b, c: ref.p2p_ref(a, b, c, 0.05))
    p2p_ref_t = _time(lambda: jax.block_until_ready(p2p_jit(z, q, mask)))
    err = float(np.linalg.norm(np.asarray(p2p_kernel()) - expect) /
                np.linalg.norm(expect))
    rows.append(("kernel_p2p_ref_jnp", p2p_ref_t, f"pallas_relerr={err:.1e}"))
    p2p_k_t = _time(lambda: jax.block_until_ready(p2p_kernel()))
    rows.append((f"kernel_p2p_pallas{mode}", p2p_k_t,
                 f"same_op_ref_us={p2p_ref_t:.1f}"))

    p = 17
    level = 4
    me = jnp.asarray(rng.normal(size=(ny, nx, p)) + 1j * rng.normal(size=(ny, nx, p)),
                     jnp.complex64)
    expect = np.asarray(ref.m2l_ref(me, level, p))          # masked-40 oracle
    m2l_fold = jax.jit(lambda g: ex.m2l_reference(g, level, p))
    m2l_t = _time(lambda: jax.block_until_ready(m2l_fold(me)))
    err = float(np.linalg.norm(np.asarray(kops.m2l_apply(me, level, p)) - expect) /
                np.linalg.norm(expect))
    rows.append(("kernel_m2l_ref_jnp", m2l_t, f"pallas_relerr={err:.1e}"))
    m2l_k_t = _time(lambda: jax.block_until_ready(kops.m2l_apply(me, level, p)))
    rows.append((f"kernel_m2l_pallas{mode}", m2l_k_t,
                 f"same_op_ref_us={m2l_t:.1f}"))

    qq = jnp.asarray(rng.normal(size=(1, 4, 128, 64)), jnp.float32)
    kk = jnp.asarray(rng.normal(size=(1, 2, 128, 64)), jnp.float32)
    expect = np.asarray(ref.attention_ref(qq, kk, kk))
    fa_t = _time(lambda: jax.block_until_ready(ref.attention_ref(qq, kk, kk)))
    err = float(np.linalg.norm(
        np.asarray(kops.flash_attention(qq, kk, kk, block_q=64, block_k=64))
        - expect) / np.linalg.norm(expect))
    rows.append(("kernel_flash_attn_ref_jnp", fa_t, f"pallas_relerr={err:.1e}"))


def bench_m2l_staging_bytes(rows, quick=False):
    """hlo_analysis check that parity folding dropped the M2L HBM traffic.

    Walks the optimized HLO of the folded reference, the pre-folding
    masked-40 formulation, and the Pallas kernel wrapper.  The folded paths
    must move fewer bytes AND contain no ``40p``-wide staging buffer (the
    old wrapper's (nb, 40p) gather tensor)."""
    import jax
    import jax.numpy as jnp
    from repro.core import expansions as ex
    from repro.kernels import ops as kops
    from repro.launch.hlo_analysis import analyze_hlo, shape_dim_pattern

    rng = np.random.default_rng(0)
    level, p = (3, 12) if quick else (4, 17)
    n = 1 << level
    me = jnp.asarray(rng.normal(size=(n, n, p)) + 1j * rng.normal(size=(n, n, p)),
                     jnp.complex64)

    def hlo(fn):
        return jax.jit(fn).lower(me).compile().as_text()

    b_old = analyze_hlo(hlo(lambda g: ex.m2l_masked40(g, level, p)))["bytes"]
    b_new = analyze_hlo(hlo(lambda g: ex.m2l_reference(g, level, p)))["bytes"]
    t_kern = hlo(lambda g: kops.m2l_apply(g, level, p))
    b_kern = analyze_hlo(t_kern)["bytes"]
    n40 = len(shape_dim_pattern(40 * p).findall(t_kern))
    rows.append(("m2l_hbm_bytes_masked40", 0.0, f"{b_old:.3e}"))
    rows.append(("m2l_hbm_bytes_folded", 0.0,
                 f"{b_new:.3e}_drop={b_old / max(b_new, 1.0):.2f}x"))
    rows.append(("m2l_kernel_wrapper_staging", 0.0,
                 f"bytes={b_kern:.3e}_40p_buffers={n40}"))


def bench_parallel_multidevice(rows, quick=False):
    """Sharded FMM wall time on forced host devices (subprocess: jax locks
    the device count at first init, and the parent runs single-device)."""
    ndev = 2 if quick else 4
    level, p = (4, 8) if quick else (5, 12)
    body = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={ndev}"
        import time
        import numpy as np
        import jax
        from jax.sharding import Mesh
        from repro.core.parallel_fmm import parallel_fmm_velocity
        from repro.core.quadtree import build_tree

        rng = np.random.default_rng(0)
        n_particles = {4000 if quick else 20000}
        pos = rng.uniform(0.02, 0.98, size=(n_particles, 2))
        tree, _ = build_tree(pos, rng.normal(size=n_particles), {level}, 0.02)
        mesh = Mesh(np.array(jax.devices()[:{ndev}]), ("data",))
        fn = lambda: jax.block_until_ready(parallel_fmm_velocity(tree, {p}, mesh))
        fn()
        t0 = time.perf_counter()
        for _ in range(3):
            fn()
        print("US", (time.perf_counter() - t0) / 3 * 1e6)
    """)
    env = _cpu_child_env()
    try:
        proc = subprocess.run([sys.executable, "-c", body], capture_output=True,
                              text=True, env=env, timeout=600)
        us = [float(l.split()[1]) for l in proc.stdout.splitlines()
              if l.startswith("US")]
        if proc.returncode != 0 or not us:
            raise RuntimeError(proc.stderr[-300:])
        rows.append((f"parallel_fmm_P{ndev}", us[0], f"L={level}_p={p}"))
    except Exception as e:  # report, never abort the whole harness
        detail = " ".join(str(e).split())[-160:].replace(",", ";")
        rows.append((f"parallel_fmm_P{ndev}", 0.0,
                     f"failed:{type(e).__name__}:{detail}"))


def bench_plan_execution(rows, quick=False):
    """Partition-driven execution plans on the Lamb-Oseen lattice (paper
    Eq 20 next to measured step time): uniform strawman vs a-priori model
    plan vs dynamic re-planning vs a 2-D block grid vs the per-axis grid
    autotuner, on forced host devices (subprocess: jax locks the device
    count at first init).

    Timing protocol: after the compile-warm step, the loop keeps stepping
    (bounded) until a step adopts no new plan/level — that step doubles as
    the warm step for whatever plan is current, so re-level/re-plan
    recompiles never land inside the timed window.  The reported time is
    the MINIMUM steady-state step (robust to host-device scheduling noise);
    any adoption that still happens while timing is counted and emitted in
    the derived field (releveled/replanned), keeping the trajectory
    comparable across PRs.  Modes run in small subprocess GROUPS: sharing
    one long-lived process let allocator/jit-cache state accumulate across
    all modes and skewed later ones (plan_dynamic read ~6% slower than
    plan_model at identical plans and programs), while full isolation
    exposes the parity comparison to minute-scale machine drift between
    subprocesses.  So model+dynamic — the pair whose parity is pinned —
    run TOGETHER with their timed steps interleaved (drift hits both
    equally; the stepper's on-device occupancy check keeps the dynamic
    replan check off the step path), and every other mode gets its own
    process.  The dynamic row reports ``vs_model`` and becomes a failed
    row (CI-fatal) outside a generous noise band.
    """
    ndev = 4
    m_side, p, steps = (120, 8, 3) if quick else (160, 12, 4)
    groups = (("uniform",), ("model", "dynamic"), ("block",), ("auto",))
    env = _cpu_child_env()
    for group in groups:
        body = textwrap.dedent(f"""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={ndev}"
            import time
            import numpy as np
            import jax
            from jax.sharding import Mesh
            from repro.core.stepper import VortexStepper
            from repro.core.vortex import lamb_oseen_particles

            pos, gamma, sigma = lamb_oseen_particles({m_side})
            mesh = Mesh(np.array(jax.devices()[:{ndev}]), ("data",))
            sts, counts = {{}}, {{}}
            for mode in {group!r}:
                grid = {{"block": (2, 2), "auto": "auto"}}.get(mode)
                st = VortexStepper(pos, gamma, sigma, p={p}, dt=0.004,
                                   mesh=mesh,
                                   plan_method="uniform" if mode == "uniform" else "model",
                                   dynamic=(mode in ("dynamic", "block", "auto")),
                                   plan_grid=grid, replan_every=2)
                st.step()                  # compile + warm
                for _ in range(4):         # settle: warm again after adoption
                    rec = st.step()
                    if not (rec.replanned or rec.releveled):
                        break
                sts[mode] = st
                counts[mode] = [0, 0, []]  # releveled, replanned, timed
            for _ in range({steps}):       # interleaved: drift is paired
                for mode in {group!r}:
                    rec = sts[mode].step()
                    counts[mode][0] += rec.releveled
                    counts[mode][1] += rec.replanned
                    counts[mode][2].append(rec.seconds)
            for mode in {group!r}:
                st = sts[mode]
                releveled, replanned, timed = counts[mode]
                us = min(timed) * 1e6
                s = st.stats()
                geom = "/".join(map(str, st.plan.rows))
                if len(getattr(st.plan, "cols", ())) > 1:
                    geom += "x" + "/".join(map(str, st.plan.cols))
                print(f"ROW plan_{{mode}} {{us:.1f}} "
                      f"LB={{s['load_balance']:.3f}}_min={{s['min_load']:.3g}}"
                      f"_max={{s['max_load']:.3g}}_rows={{geom}}"
                      f"_releveled={{releveled}}_replanned={{replanned}}")
        """)
        try:
            proc = subprocess.run([sys.executable, "-c", body],
                                  capture_output=True, text=True, env=env,
                                  timeout=1800)
            got = [l.split(maxsplit=3) for l in proc.stdout.splitlines()
                   if l.startswith("ROW")]
            if proc.returncode != 0 or len(got) != len(group):
                raise RuntimeError(proc.stderr[-300:])
            by_mode = {name: (float(us), derived)
                       for _, name, us, derived in got}
            for name, (us, derived) in by_mode.items():
                if name == "plan_dynamic" and "plan_model" in by_mode:
                    ratio = us / by_mode["plan_model"][0]
                    derived += f"_vs_model={ratio:.2f}x"
                    # the pin: paired steady-state dynamic stepping must
                    # stay within noise of the static model plan
                    if not 0.75 <= ratio <= 1.33:
                        derived = "failed:parity_" + derived
                rows.append((name, us, derived))
        except Exception as e:  # report, never abort the whole harness
            detail = " ".join(str(e).split())[-160:].replace(",", ";")
            for mode in group:
                rows.append((f"plan_{mode}", 0.0,
                             f"failed:{type(e).__name__}:{detail}"))


def bench_overlap(rows, quick=False):
    """Interior/rim overlapped execution vs the monolithic ordering
    (DESIGN.md §9), plus the fused packed P2P exchange, on 4 forced host
    devices (subprocess: jax locks the device count at first init).

    ``overlap_on`` / ``overlap_off`` time the full sharded FMM with the
    halo collectives hidden behind tile-interior compute vs the serial
    exchange-then-compute ordering (interleaved reps, min per mode — the
    two modes share one process so the comparison is paired).
    ``p2p_exchange_fused`` times the ONE packed (z, q, mask) ``_tile_halo``
    round against the three separate exchanges it replaced and counts the
    ``collective-permute`` ops in the lowered HLO of each (3x reduction,
    12 -> 4 on a 2x2 grid).

    Runs at the full problem size even under ``--quick``: overlap pays off
    when the tile interiors are big enough to hide the exchange (the
    production regime); at toy tile sizes the extra rim launches dominate
    and the row would misrepresent the trade.
    """
    ndev = 4
    m_side, level, p = (160, 6, 12)
    body = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={ndev}"
        import time
        import numpy as np
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.core import parallel_fmm as pf
        from repro.core.cost_model import ModelParams
        from repro.core.plan import plan_from_counts
        from repro.core.quadtree import build_tree
        from repro.core.vortex import lamb_oseen_particles

        mesh = Mesh(np.array(jax.devices()[:{ndev}]), ("data",))
        pos, gamma, sigma = lamb_oseen_particles({m_side})
        tree, index = build_tree(pos, gamma, level={level}, sigma=sigma)
        params = ModelParams(level={level}, cut=4, p={p}, slots=tree.slots)
        plan = plan_from_counts(index.counts, params, {ndev}, method="model")

        fns = {{}}
        for ov in (True, False):
            fn = (lambda ov=ov: jax.block_until_ready(
                pf.parallel_fmm_velocity(tree, {p}, mesh, plan=plan,
                                         overlap=ov)))
            fn()                               # compile + warm
            fns[ov] = fn
        t = {{True: [], False: []}}
        for _ in range(6):                     # interleaved, paired reps
            for ov in (False, True):
                t0 = time.perf_counter()
                fns[ov]()
                t[ov].append(time.perf_counter() - t0)
        on, off = min(t[True]) * 1e6, min(t[False]) * 1e6
        # the pin: overlapped execution must not lose to the serial
        # ordering (10% jitter allowance for shared CI runners); a
        # violation marks the row failed, which the CI guard treats as
        # fatal
        tag = "" if on <= 1.10 * off else "failed:overlap_slower_"
        print(f"ROW overlap_on {{on:.1f}} {{tag}}"
              f"hidden_vs_serial={{off / on:.2f}}x_rows="
              + "/".join(map(str, plan.rows)))
        print(f"ROW overlap_off {{off:.1f}} serial_comm_baseline")

        # fused packed P2P exchange vs the three separate rounds it replaced
        # (2x2 grid: the full two-axis exchange, 4 ppermutes per round)
        grid = (2, 2)
        rmax = cmax = (1 << {level}) // 2
        rv = cv = rmax
        def fused(z, q, m):
            buf = pf._tile_halo(pf._pack_particles(z, q, m), 1, rv, cv,
                                "data", grid)
            return pf._unpack_particles(buf, z.dtype)
        def unfused(z, q, m):
            return (pf._tile_halo(z, 1, rv, cv, "data", grid),
                    pf._tile_halo(q, 1, rv, cv, "data", grid),
                    pf._tile_halo(m, 1, rv, cv, "data", grid))
        spec = P("data", None, None)
        rng = np.random.default_rng(0)
        s = tree.slots
        shape = ({ndev} * rmax, cmax, s)
        z = jnp.asarray(rng.normal(size=shape) + 1j * rng.normal(size=shape),
                        jnp.complex64)
        q = z * 0.5
        m = jnp.asarray(rng.uniform(size=shape) > 0.3)
        from repro.analysis import contracts as C
        cc = C.collective_count("collective-permute", 4)
        stats = {{}}
        lows = {{}}
        for name, fn in (("fused", fused), ("unfused", unfused)):
            jfn = jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=(spec,) * 3,
                                        out_specs=(spec,) * 3, check_vma=False))
            lows[name] = C.Lowered(jfn, z, q, m, label=name)
            nperm = cc.measure(lows[name].text(cc.ir))
            jax.block_until_ready(jfn(z, q, m))
            t0 = time.perf_counter()
            for _ in range(20):
                jax.block_until_ready(jfn(z, q, m))
            stats[name] = ((time.perf_counter() - t0) / 20 * 1e6, nperm)
        (fus, nf), (unf, nu) = stats["fused"], stats["unfused"]
        # the pin, now through the contract registry: the packed exchange
        # compiles to exactly 4 collective-permutes (TRUE instance counts
        # in the optimized HLO — the old regex counted textual mentions),
        # a 3x reduction vs the three rounds it replaced
        (res,) = C.evaluate(lows["fused"], [cc])
        tag = "" if res.ok and nu == 3 * nf else "failed:collective_count_"
        print(f"ROW p2p_exchange_fused {{fus:.1f}} {{tag}}"
              f"collectives={{nf}}_was={{nu}}_unfused_us={{unf:.1f}}")
    """)
    env = _cpu_child_env()
    names = ("overlap_on", "overlap_off", "p2p_exchange_fused")
    try:
        proc = subprocess.run([sys.executable, "-c", body], capture_output=True,
                              text=True, env=env, timeout=1800)
        got = [l.split(maxsplit=3) for l in proc.stdout.splitlines()
               if l.startswith("ROW")]
        if proc.returncode != 0 or len(got) != len(names):
            raise RuntimeError(proc.stderr[-300:])
        for _, name, us, derived in got:
            rows.append((name, float(us), derived))
    except Exception as e:  # report, never abort the whole harness
        detail = " ".join(str(e).split())[-160:].replace(",", ";")
        for name in names:
            rows.append((name, 0.0, f"failed:{type(e).__name__}:{detail}"))


def bench_pipeline(rows, quick=False):
    """Substep-pipelined asynchrony vs the serial issue order (DESIGN.md
    §12) on 4 forced host devices (subprocess: jax locks the device count
    at first init).

    ``pipeline_on`` / ``pipeline_off`` time the full RK2 step with the
    cross-substep P2P prefetch + gather/root-tree overlap vs the
    pre-pipeline ordering (interleaved reps, min per mode; paired in one
    process).  Host CPU collectives cannot actually overlap compute, so
    the pin is the one that transfers to real backends: pipelining must
    not LOSE (<= 1.10x, jitter allowance), while the issue-order win is
    pinned structurally in ``gather_overlap``.

    ``gather_overlap`` parses both lowered StableHLO modules (trace order
    is preserved) and reports the cut-level all_gather's *issue depth* —
    dot_generals between issue and first consumption.  Pins, evaluated
    through the trace-contract registry (repro/analysis/contracts):
    ``issue_depth_grows`` — depth must GROW under pipelining (that window
    is what the GPU latency-hiding scheduler fills) with EQUAL
    collective_permute counts across modes (the prefetch replaces the
    exchange, never duplicates it) — and ``min_issue_depth`` as an
    absolute floor.  Violations mark the row failed:, CI-fatal.
    """
    ndev = 4
    m_side, level, p = (80, 5, 8) if quick else (160, 6, 12)
    depth_floor = 8 if quick else 32
    body = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={ndev}"
        import time
        import numpy as np
        import jax
        from jax.sharding import Mesh
        from repro.core import parallel_fmm as pf
        from repro.core.cost_model import ModelParams
        from repro.core.plan import plan_from_counts
        from repro.core.quadtree import build_tree
        from repro.core.stepper import rk2_step
        from repro.core.vortex import lamb_oseen_particles
        from repro.launch.hlo_analysis import collective_issue_depths

        mesh = Mesh(np.array(jax.devices()[:{ndev}]), ("data",))
        pos, gamma, sigma = lamb_oseen_particles({m_side})
        tree, index = build_tree(pos, gamma, level={level}, sigma=sigma)
        params = ModelParams(level={level}, cut=4, p={p}, slots=tree.slots)
        plan = plan_from_counts(index.counts, params, {ndev}, method="model")

        fns = {{}}
        for pl in (True, False):
            fn = (lambda pl=pl: jax.block_until_ready(rk2_step(
                tree, 1e-4, p={p}, mesh=mesh, plan=plan,
                pipeline=pl)[0].z))
            fn()                               # compile + warm
            fns[pl] = fn
        t = {{True: [], False: []}}
        for _ in range(10):                    # interleaved, paired reps
            for pl in (False, True):
                t0 = time.perf_counter()
                fns[pl]()
                t[pl].append(time.perf_counter() - t0)
        on, off = min(t[True]) * 1e6, min(t[False]) * 1e6
        tag = "" if on <= 1.10 * off else "failed:pipeline_slower_"
        print(f"ROW pipeline_on {{on:.1f}} {{tag}}"
              f"vs_serial_order={{off / on:.2f}}x_rows="
              + "/".join(map(str, plan.rows)))
        print(f"ROW pipeline_off {{off:.1f}} serial_issue_order_baseline")

        # structural pin, through the contract registry: the cut-level
        # all_gather's issue depth must GROW under pipelining (and clear
        # an absolute floor) while permute counts stay equal (prefetch
        # replaces, never duplicates)
        from repro.analysis import contracts as C
        entry = pf.TRACE_ENTRY_POINTS["parallel_fmm_evaluate"]
        lows = {{pl: C.Lowered(entry, tree, {p}, mesh, plan=plan,
                               pipeline=pl, label="pipeline=" + str(pl))
                for pl in (True, False)}}
        res = C.evaluate(lows[True],
                         [C.issue_depth_grows("all_gather"),
                          C.min_issue_depth("all_gather", {depth_floor})],
                         pair_with=lows[False])
        depths = {{pl: collective_issue_depths(lows[pl].stablehlo)
                  for pl in (True, False)}}
        ag_on = max(depths[True]["all_gather"], default=0)
        ag_off = max(depths[False]["all_gather"], default=0)
        np_on = len(depths[True]["collective_permute"])
        np_off = len(depths[False]["collective_permute"])
        tag = "" if not C.violations(res) else "failed:issue_order_"
        print(f"ROW gather_overlap {{float(ag_on):.1f}} {{tag}}"
              f"gather_issue_depth={{ag_on}}_was={{ag_off}}"
              f"_permutes={{np_on}}_was={{np_off}}")
    """)
    env = _cpu_child_env()
    names = ("pipeline_on", "pipeline_off", "gather_overlap")
    try:
        proc = subprocess.run([sys.executable, "-c", body],
                              capture_output=True, text=True, env=env,
                              timeout=1800)
        got = [l.split(maxsplit=3) for l in proc.stdout.splitlines()
               if l.startswith("ROW")]
        if proc.returncode != 0 or len(got) != len(names):
            raise RuntimeError(proc.stderr[-300:])
        for _, name, us, derived in got:
            rows.append((name, float(us), derived))
    except Exception as e:  # report, never abort the whole harness
        detail = " ".join(str(e).split())[-160:].replace(",", ";")
        for name in names:
            rows.append((name, 0.0, f"failed:{type(e).__name__}:{detail}"))


def bench_guarded_step(rows, quick=False):
    """Guarded vs unguarded RK2 step on 4 forced host devices.

    The health word (DESIGN.md §11) is computed inside the step's own
    device program — a handful of finiteness reductions riding the
    existing outputs, no extra host sync — so guarded throughput must
    stay within 3% of unguarded.  Interleaved paired reps, min per mode;
    a violation marks the row ``failed:``, which the CI guard treats as
    fatal."""
    ndev = 4
    m_side, level, p = (80, 5, 8) if quick else (160, 6, 12)
    body = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={ndev}"
        import time
        import numpy as np
        import jax
        from jax.sharding import Mesh
        from repro.core.cost_model import ModelParams
        from repro.core.plan import plan_from_counts
        from repro.core.quadtree import build_tree
        from repro.core.stepper import rk2_step
        from repro.core.vortex import lamb_oseen_particles

        mesh = Mesh(np.array(jax.devices()[:{ndev}]), ("data",))
        pos, gamma, sigma = lamb_oseen_particles({m_side})
        tree, index = build_tree(pos, gamma, level={level}, sigma=sigma)
        params = ModelParams(level={level}, cut=4, p={p}, slots=tree.slots)
        plan = plan_from_counts(index.counts, params, {ndev}, method="model")

        fns = {{}}
        for g in (True, False):
            fn = (lambda g=g: jax.block_until_ready(rk2_step(
                tree, 1e-4, p={p}, mesh=mesh, plan=plan, guard=g)[0].z))
            fn()                               # compile + warm
            fns[g] = fn
        t = {{True: [], False: []}}
        for _ in range(10):                    # interleaved, paired reps
            for g in (False, True):
                t0 = time.perf_counter()
                fns[g]()
                t[g].append(time.perf_counter() - t0)
        gu, un = min(t[True]) * 1e6, min(t[False]) * 1e6
        ratio = gu / un
        tag = "" if ratio <= 1.03 else "failed:guard_overhead_"
        print(f"ROW guarded_step_overhead {{gu:.1f}} {{tag}}"
              f"ratio={{ratio:.3f}}_unguarded_us={{un:.1f}}")
    """)
    env = _cpu_child_env()
    try:
        proc = subprocess.run([sys.executable, "-c", body],
                              capture_output=True, text=True, env=env,
                              timeout=1800)
        got = [l.split(maxsplit=3) for l in proc.stdout.splitlines()
               if l.startswith("ROW")]
        if proc.returncode != 0 or len(got) != 1:
            raise RuntimeError(proc.stderr[-300:])
        for _, name, us, derived in got:
            rows.append((name, float(us), derived))
    except Exception as e:  # report, never abort the whole harness
        detail = " ".join(str(e).split())[-160:].replace(",", ";")
        rows.append(("guarded_step_overhead", 0.0,
                     f"failed:{type(e).__name__}:{detail}"))


def bench_plan_halo(rows, quick=False):
    """1-D band vs 2-D block halo volume on the Lamb-Oseen lattice (the
    BlockPlan's reason to exist — ROADMAP "2-D execution plans").

    ``halo_model_P*`` prices the valid-extent (modeled) ppermute bytes per
    FMM evaluation; ``halo_exec_P*`` prices what the driver literally
    transfers (padded extents + corner-carrying strips).  Host-side only —
    no devices needed."""
    from repro.core.cost_model import ModelParams
    from repro.core.plan import halo_volume, plan_from_counts
    from repro.core.quadtree import build_tree
    from repro.core.vortex import lamb_oseen_particles

    level = 5 if quick else 6
    pos, gamma, sigma = lamb_oseen_particles(120 if quick else 160)
    tree, index = build_tree(pos, gamma, level, sigma)
    params = ModelParams(level=level, cut=4, p=12, slots=tree.slots)
    grids = {4: (2, 2), 8: (4, 2), 16: (4, 4)}
    for P in (4, 8) if quick else (4, 8, 16):
        slab = plan_from_counts(index.counts, params, P, method="model")
        block = plan_from_counts(index.counts, params, P, method="model",
                                 grid=grids[P])
        for tag, executed in (("model", False), ("exec", True)):
            hs = halo_volume(slab, params, executed=executed)["total"]
            hb = halo_volume(block, params, executed=executed)["total"]
            rows.append((f"halo_{tag}_P{P}", 0.0,
                         f"slab={hs:.3e}_block={hb:.3e}"
                         f"_ratio={hs / hb:.2f}x"))


def bench_equations(rows, quick=False):
    """The pluggable equation subsystem (DESIGN.md §10): wall time of the
    two new workloads next to the vortex baseline, same tree, same slab
    path.

    ``eq_laplace_step`` times one full Laplace evaluation (potential +
    field from ONE downward sweep — the 2-channel analogue of a vortex
    velocity step); ``eq_tracer_eval`` times the passive probe-grid
    evaluation (sources' expansions + near field at a separate target
    batch).  Derived fields carry the f64 direct-sum relative error of a
    subsample, so the rows double as numerics smoke."""
    import jax
    from repro.core import equations as eqs
    from repro.core.fmm import fmm_evaluate, fmm_velocity
    from repro.core.quadtree import build_tree, gather_particle_values

    n_particles, level, p = (20_000, 5, 12) if quick else (100_000, 6, 17)
    rng = np.random.default_rng(0)
    pos = rng.uniform(0.01, 0.99, (n_particles, 2))
    strength = rng.normal(size=n_particles)
    # sigma well under the leaf box size so the mollifier is ~1 at
    # interaction-list distance and the relerr fields measure the
    # implementation, not Type-I kernel-substitution error (paper §3)
    sigma = 0.25 / 2 ** level

    vtree, _ = build_tree(pos, strength, level, sigma)
    vortex_t = _time(lambda: jax.block_until_ready(fmm_velocity(vtree, p)))

    ltree, lindex = build_tree(pos, strength, level, sigma,
                               charge_scale=eqs.LAPLACE.charge_scale)
    lap = lambda: jax.block_until_ready(
        fmm_evaluate(ltree, p, eq=eqs.LAPLACE))
    lap_t = _time(lap)
    out = np.asarray(fmm_evaluate(ltree, p, eq=eqs.LAPLACE))
    sel = rng.choice(n_particles, size=400, replace=False)
    z = pos[:, 0] + 1j * pos[:, 1]
    exact = eqs.direct_sum(eqs.LAPLACE, z[sel], z, strength, sigma=sigma)
    pot = gather_particle_values(out[..., 0], lindex)[sel].real
    err = float(np.linalg.norm(pot - exact[:, 0].real) /
                np.linalg.norm(exact[:, 0].real))
    rows.append(("eq_laplace_step", lap_t,
                 f"C=2_vs_vortex={lap_t / max(vortex_t, 1e-9):.2f}x"
                 f"_relerr={err:.1e}"))

    m = int(np.sqrt(n_particles // 4))
    xs = np.linspace(0.05, 0.95, m)
    PX, PY = np.meshgrid(xs, xs, indexing="xy")
    probes = np.stack([PX.ravel(), PY.ravel()], axis=1)
    targets, tindex = build_tree(probes, np.zeros(len(probes)), level, sigma)
    trc = lambda: jax.block_until_ready(
        fmm_evaluate(vtree, p, eq=eqs.TRACER, targets=targets))
    trc_t = _time(trc)
    got = gather_particle_values(
        np.asarray(fmm_evaluate(vtree, p, eq=eqs.TRACER, targets=targets)),
        tindex)
    tsel = rng.choice(len(probes), size=400, replace=False)
    tz = probes[tsel, 0] + 1j * probes[tsel, 1]
    texact = eqs.direct_sum(eqs.TRACER, tz, z, strength, sigma=sigma)
    terr = float(np.linalg.norm(got[tsel] - texact) /
                 np.linalg.norm(texact))
    rows.append(("eq_tracer_eval", trc_t,
                 f"targets={len(probes)}_relerr={terr:.1e}"))


def bench_moe_placement(rows, quick=False):
    """The paper's technique transplanted: expert-placement load balance."""
    from repro.models.moe import expert_placement
    rng = np.random.default_rng(0)
    E, ranks = 64, 8
    counts = (rng.zipf(1.5, E) * 100).clip(0, 50_000).astype(np.float64)
    coact = np.zeros((E, E))
    assign = expert_placement(counts, coact, ranks)
    loads = np.bincount(assign, weights=counts, minlength=ranks)
    naive = counts.reshape(ranks, -1).sum(1)
    rows.append(("moe_placement_lb", 0.0,
                 f"model={loads.min()/max(loads.max(),1):.3f}_"
                 f"contiguous={naive.min()/max(naive.max(),1):.3f}"))


def bench_trace_contracts(rows, quick=False):
    """The static-analysis layer as a benchmark row: run the serial
    trace-contract catalog (M2L no-staging + fewer-bytes, guard-free and
    callback-free traces, no donation on ``rk2_step``, no f64 upcasts)
    plus the repo lint pass in-process, and report checked/violations.
    Any violation marks the row ``failed:``, which the CI guard treats as
    fatal.  The multidevice contracts (fused-exchange counts, pipelined
    issue depth, SPMD schedule consistency, retrace session) run in the
    dedicated static-analysis CI job via ``python -m
    repro.analysis.check``."""
    try:
        import pathlib

        import jax
        import jax.numpy as jnp

        from repro.analysis import contracts as C
        from repro.analysis import lint as L
        from repro.core import expansions as ex
        from repro.core.fmm import fmm_velocity
        from repro.core.quadtree import build_tree
        from repro.core.stepper import TRACE_ENTRY_POINTS
        from repro.kernels import ops as kops

        level, p = (3, 12) if quick else (4, 17)
        n = 1 << level
        rng = np.random.default_rng(0)
        me = jnp.asarray(rng.normal(size=(n, n, p)) +
                         1j * rng.normal(size=(n, n, p)), jnp.complex64)
        kern = C.Lowered(jax.jit(lambda g: kops.m2l_apply(g, level, p)), me,
                         label="m2l_apply")
        fold = C.Lowered(jax.jit(lambda g: ex.m2l_reference(g, level, p)),
                         me, label="m2l_reference")
        m40 = C.Lowered(jax.jit(lambda g: ex.m2l_masked40(g, level, p)), me,
                        label="m2l_masked40")
        pos = rng.uniform(0.05, 0.95, size=(600, 2))
        tree, _ = build_tree(pos, rng.normal(size=600), 3, sigma=0.02)
        drv = C.Lowered(jax.jit(lambda t: fmm_velocity(t, p=6)), tree,
                        label="fmm_velocity")
        rk2 = C.Lowered(TRACE_ENTRY_POINTS["rk2_step"], tree, 1e-4, p=6,
                        label="rk2_step")

        staging = [C.no_staging_dim(40 * p), C.no_f64_upcast()]
        results = C.evaluate(kern, staging) + C.evaluate(fold, staging)
        results += C.evaluate(fold, [C.fewer_bytes("folded", "masked40")],
                              pair_with=m40)
        results += C.evaluate(drv, [C.sentinel_free(), C.no_host_callback(),
                                    C.no_f64_upcast()])
        results += C.evaluate(rk2, [C.sentinel_free(), C.not_donated("rk2"),
                                    C.no_host_callback()])

        src_root = pathlib.Path(__file__).resolve().parents[1] / "src" / \
            "repro"
        findings = L.run_lint(src_root)
        checked = len(results) + len(L.DEFAULT_RULES)
        nviol = len(C.violations(results)) + len(findings)
        tag = "" if nviol == 0 else "failed:"
        rows.append(("trace_contracts", 0.0,
                     f"{tag}checked={checked}_violations={nviol}"))
    except Exception as e:  # report, never abort the whole harness
        detail = " ".join(str(e).split())[-160:].replace(",", ";")
        rows.append(("trace_contracts", 0.0,
                     f"failed:{type(e).__name__}:{detail}"))


def bench_serve(rows, quick=False):
    """FMM-as-a-service throughput/latency (DESIGN.md §15), subprocess.

    ``serve_batched`` / ``serve_sequential``: the SAME wave of tiny
    same-bucket one-shot jobs served through the vmap bin-packing engine
    vs an engine capped at ``batch_capacities=(1,)`` (one device program
    per job).  Paired-interleaved reps, min per mode; trees are pulled
    from each engine's warm artifact cache, so the pair isolates
    dispatch + execution.  Pins: batched throughput >= 1.5x sequential
    (failed: below 1.35x, the pipeline_on-style 10% jitter band) at
    EQUAL results (1e-5), and zero steady-state retraces
    (``batched_cache_entries`` flat across reps, failed: otherwise —
    CI-fatal via the no-silently-failed-rows guard).

    ``serve_throughput`` reports requests/s of the batched engine;
    ``serve_latency`` reports p50/p99 per job class (batched one-shots +
    RK2 session steps) from the engine's own latency counters.
    """
    n_jobs, reps, steps = (8, 3, 1) if quick else (12, 6, 2)
    body = textwrap.dedent(f"""
        import time
        import numpy as np
        from repro.serve import fmm_service as svc

        n_jobs, n = {n_jobs}, 60
        rng = np.random.default_rng(0)
        pos = rng.uniform(0.1, 0.9, size=(n, 2))
        qs = [rng.normal(size=n) for _ in range(n_jobs)]
        waves = {{m: qs for m in ("batched", "sequential")}}  # same jobs
        engines = {{
            "batched": svc.FmmServiceEngine(),
            "sequential": svc.FmmServiceEngine(batch_capacities=(1,)),
        }}

        def wave(mode):
            eng = engines[mode]
            jids = [eng.submit(svc.FmmJob(positions=pos, strength=q, p=4,
                                          sigma=0.02, tenant=mode))
                    for q in waves[mode]]
            eng.drain()
            return [np.asarray(eng.result(j).out) for j in jids]

        out = {{m: wave(m) for m in engines}}      # compile + warm caches
        entries_warm = svc.batched_cache_entries()
        for eng in engines.values():
            eng._latencies.clear()                 # drop compile-wave tails
        t = {{m: [] for m in engines}}
        for _ in range({reps}):                    # interleaved, paired
            for m in ("sequential", "batched"):
                t0 = time.perf_counter()
                out[m] = wave(m)
                t[m].append(time.perf_counter() - t0)
        retraces = svc.batched_cache_entries() - entries_warm

        err = max(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30)
                  for a, b in zip(out["batched"], out["sequential"]))
        bat = min(t["batched"]) * 1e6 / n_jobs     # us per job
        seq = min(t["sequential"]) * 1e6 / n_jobs
        tag = ""
        if err > 1e-5:
            tag = "failed:batched_results_diverge_"
        elif seq < 1.35 * bat:
            tag = "failed:batched_speedup_below_band_"
        print(f"ROW serve_batched {{bat:.1f}} {{tag}}"
              f"vs_sequential={{seq / bat:.2f}}x_err={{err:.1e}}"
              f"_jobs={{n_jobs}}")
        print(f"ROW serve_sequential {{seq:.1f}} one_program_per_job")

        tag = "" if retraces == 0 else "failed:steady_state_retraced_"
        print(f"ROW serve_throughput {{bat:.1f}} {{tag}}req_s="
              f"{{1e6 / bat:.0f}}_retraces={{retraces}}"
              f"_entries={{entries_warm}}")

        eng = engines["batched"]
        sid = eng.submit(svc.FmmJob(positions=pos,
                                    strength=0.1 * rng.normal(size=n),
                                    steps={steps}, p=4, dt=1e-3, sigma=0.02))
        for _ in range({steps}):
            eng.step_session(sid)
        lat = eng.stats()["latency"]
        b, s = lat["batched"], lat["session"]
        print(f"ROW serve_latency {{b['p50_ms'] * 1e3:.1f}} "
              f"batched_p50={{b['p50_ms']:.1f}}ms_p99={{b['p99_ms']:.1f}}ms"
              f"_session_p50={{s['p50_ms']:.0f}}ms_p99="
              f"{{s['p99_ms']:.0f}}ms")
    """)
    env = _cpu_child_env()
    names = ("serve_batched", "serve_sequential", "serve_throughput",
             "serve_latency")
    try:
        proc = subprocess.run([sys.executable, "-c", body],
                              capture_output=True, text=True, env=env,
                              timeout=1800)
        got = [l.split(maxsplit=3) for l in proc.stdout.splitlines()
               if l.startswith("ROW")]
        if proc.returncode != 0 or len(got) != len(names):
            raise RuntimeError(proc.stderr[-300:])
        for _, name, us, derived in got:
            rows.append((name, float(us), derived))
    except Exception as e:  # report, never abort the whole harness
        detail = " ".join(str(e).split())[-160:].replace(",", ";")
        for name in names:
            rows.append((name, 0.0, f"failed:{type(e).__name__}:{detail}"))


def bench_proc_fault_recovery(rows, quick=False):
    """MTTR of the cross-process fault-tolerance path (DESIGN.md §14): a
    2-rank kill drill through ``launch/supervisor.py`` — SIGKILL rank 1
    mid-step, survivors agree, shrink to 1, ``from_checkpoint``-restore,
    finish.  us_per_call is the mean time to recovery (detection +
    teardown/agreement/restore + first post-restore step); the pieces ride
    in ``derived``.  Any failure (including an unfinished drill) marks the
    row ``failed:``, which the CI guard treats as fatal."""
    import tempfile

    from repro.core.faults import FaultInjector, FaultSpec
    from repro.launch.supervisor import Supervisor, SupervisorConfig
    from repro.parallel import resilience as rz

    try:
        with tempfile.TemporaryDirectory(prefix="fmm-drill-") as d:
            cfg = SupervisorConfig(
                world=2, target_step=4, coord_dir=d, n_side=16, p=4,
                dt=0.004, checkpoint_every=1, checkpoint_keep=8,
                watchdog=rz.WatchdogPolicy(compile_grace=900.0,
                                           teardown_grace=30.0),
                restart=rz.RestartPolicy(min_world=1, backoff_base=0.05),
                max_wall=1500.0)
            sup = Supervisor(cfg, faults=FaultInjector(
                FaultSpec(site="proc_kill", step=2, device=1)))
            result = sup.run()
            if not result.success or len(result.faults) != 1:
                raise RuntimeError(f"drill did not recover: "
                                   f"{len(result.faults)} faults")
            rep = result.faults[0]
            parts = [rep.detect_seconds, rep.restore_seconds,
                     rep.first_step_seconds]
            if any(p is None for p in parts):
                raise RuntimeError(f"MTTR piece missing: {parts}")
            mttr = sum(parts)
            rows.append(("proc_fault_recovery", mttr * 1e6,
                         f"detect={rep.detect_seconds:.2f}s_restore="
                         f"{rep.restore_seconds:.2f}s_first_step="
                         f"{rep.first_step_seconds:.2f}s_world="
                         f"{rep.world_before}to{rep.world_after}"))
    except Exception as e:  # report, never abort the whole harness
        detail = " ".join(str(e).split())[-160:].replace(",", ";")
        rows.append(("proc_fault_recovery", 0.0,
                     f"failed:{type(e).__name__}:{detail}"))


def main() -> None:
    quick = "--quick" in sys.argv
    json_path = None
    if "--json" in sys.argv:
        i = sys.argv.index("--json")
        if i + 1 >= len(sys.argv) or sys.argv[i + 1].startswith("--"):
            sys.exit("usage: python -m benchmarks.run [--quick] [--json PATH]")
        json_path = sys.argv[i + 1]
    from repro.configs.backend import use_compile_cache
    use_compile_cache()
    rows: list[tuple[str, float, str]] = []
    for bench in (bench_fig6_stage_timings, bench_fig7_9_scaling,
                  bench_table12_memory, bench_kernels, bench_m2l_staging_bytes,
                  bench_parallel_multidevice, bench_plan_execution,
                  bench_overlap, bench_pipeline, bench_guarded_step,
                  bench_plan_halo,
                  bench_equations,
                  bench_serve,
                  bench_trace_contracts,
                  bench_proc_fault_recovery,
                  bench_moe_placement):
        bench(rows, quick=quick)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    if json_path:
        with open(json_path, "w") as f:
            json.dump([{"name": n, "us_per_call": round(u, 1), "derived": d}
                       for n, u, d in rows], f, indent=2)
        print(f"# wrote {json_path}", file=sys.stderr)
    failed = [name for name, _, derived in rows
              if derived.startswith("failed:")]
    if failed:
        sys.exit(f"failed rows: {', '.join(failed)}")


if __name__ == "__main__":
    main()
