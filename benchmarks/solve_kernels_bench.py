"""Solve-kernel benchmarks: fused γ-sweep, batched factor kernels, the
Woodbury sweep-handle crossover, and the tiled-Gram d=6144 sharded solve.

The headline numbers behind ISSUE 5's acceptance bar, recorded in
``results/bench/solve_kernels_bench.json``:

  * ``fused_sweep`` — the fused Pallas multi-γ kernel (interpret mode on
    this CPU host) vs the PR-3 per-γ host loop (fresh ``C + γI`` + LAPACK
    per γ) and vs the one-eigendecomposition host sweep, at d=2048 / 16 γs.
    Acceptance: fused ≥ 2× the per-γ host loop.
  * ``batched_factor`` — blocked-Cholesky + batched-substitution kernels vs
    a numpy loop over the same batch.
  * ``sweep_handle`` — repeated ``solve_multi_gamma`` on an evolving
    federation: Woodbury-updated eigendecomposition handle vs re-eigh per
    sweep, as pending rank grows (the d/8 budget guidance).
  * ``tiled_6144`` — the tiled-Gram ``ShardedCoordinator`` solving a
    d=6144 head on an 8-way (host-platform) mesh under x64, with per-shard
    parity vs the sync host path and resident-memory accounting. Runs in a
    subprocess because both x64 and the device count are process-global.
  * ``distributed_factor`` — ISSUE 6's tile-parallel distributed Cholesky
    vs gather-then-factor on an 8-way mesh at d∈{2048, 4096, 6144, 8192}
    (x64 subprocess per d). Records wall time, the peak per-device
    transient from the jaxpr (``peak_aval_bytes``) and the 1e-10 parity
    bar. The gather baseline only runs where its (d, d) per-device
    transient fits ``DEVICE_TRANSIENT_BUDGET`` (256 MiB) — at d=6144
    (302 MiB) and d=8192 (512 MiB) it is recorded as infeasible, which is
    the point: the distributed factor tops out at the (d/8, d) row tile
    and keeps going.

``--smoke`` shrinks every case (CI scale); ``python -m benchmarks.run``
registers this module and folds its wall times into the
``results/bench/BENCH_solve.json`` trajectory (gated run-over-run by
``tools/bench_gate.py``).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np

from benchmarks.common import print_table


def _time(fn, repeat=3):
    best = float("inf")
    for _ in range(repeat):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _gram(d, seed=0, n_mult=4):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n_mult * d, d))
    return x.T @ x, x


def bench_fused_sweep(d, c, n_gammas, repeat=3):
    """Fused Pallas sweep vs per-γ host loop vs one-eigh host sweep."""
    import jax.numpy as jnp

    from repro.core.engine import AnalyticEngine, SuffStats
    from repro.kernels import ops

    try:
        from scipy.linalg import solve_triangular
    except ImportError:                                  # pragma: no cover
        solve_triangular = None

    rng = np.random.default_rng(0)
    gram, x = _gram(d)
    q = x.T @ np.eye(c)[rng.integers(0, c, x.shape[0])]
    gammas = np.logspace(-3, 2, n_gammas)

    def host_loop():
        # the PR-3 per-γ path: materialize C + γI and factor, per γ
        # (exactly what `for g in gammas: engine.solve(stats, g)` costs)
        out = []
        for g in gammas:
            a = gram + g * np.eye(d)
            r = np.linalg.cholesky(a)
            if solve_triangular is not None:
                y = solve_triangular(r, q, lower=True)
                out.append(solve_triangular(r, y, lower=True, trans="T"))
            else:
                out.append(np.linalg.solve(a, q))
        return out

    eng = AnalyticEngine("numpy_f64", gamma=1.0)
    stats = SuffStats(gram=gram, moment=q, count=float(x.shape[0]),
                      clients=1.0)

    def eigh_sweep():
        return eng.solve_multi_gamma(stats, gammas)

    cj = jnp.asarray(gram, jnp.float32)
    qj = jnp.asarray(q, jnp.float32)
    gj = jnp.asarray(gammas, jnp.float32)

    def fused():
        np.asarray(ops.multi_gamma_solve(cj, qj, gj))

    fused()                                              # compile once
    t_loop = _time(host_loop, repeat)
    t_eigh = _time(eigh_sweep, repeat)
    t_fused = _time(fused, repeat)
    # accuracy of the f32 kernel sweep vs the f64 host loop
    ws = np.asarray(ops.multi_gamma_solve(cj, qj, gj), np.float64)
    ref = host_loop()
    err = max(np.abs(ws[i] - ref[i]).max() / np.abs(ref[i]).max()
              for i in range(n_gammas))
    return dict(bench="fused_sweep", d=d, c=c, n_gammas=n_gammas,
                host_loop_s=t_loop, eigh_sweep_s=t_eigh, fused_s=t_fused,
                speedup_vs_loop=t_loop / t_fused,
                speedup_vs_eigh=t_eigh / t_fused,
                fused_rel_err=float(err))


def bench_batched_factor(d, c, batch, repeat=3):
    """Batched blocked-Cholesky/substitution kernels vs a numpy loop."""
    import jax.numpy as jnp

    from repro.kernels import ops

    rng = np.random.default_rng(1)
    mats = np.stack([_gram(d, seed=i)[0] + np.eye(d) for i in range(batch)])
    rhs = rng.standard_normal((batch, d, c))

    def host():
        for i in range(batch):
            r = np.linalg.cholesky(mats[i])
            np.linalg.solve(mats[i], rhs[i])
            del r

    aj = jnp.asarray(mats, jnp.float32)
    bj = jnp.asarray(rhs, jnp.float32)

    def kernel():
        l = ops.blocked_cholesky(aj)
        np.asarray(ops.cholesky_solve(l, bj))

    kernel()                                             # compile once
    t_host = _time(host, repeat)
    t_kernel = _time(kernel, repeat)
    return dict(bench="batched_factor", d=d, c=c, batch=batch,
                host_s=t_host, kernel_s=t_kernel,
                speedup=t_host / t_kernel)


def bench_sweep_handle(d, c, n_gammas, ranks, repeat=3):
    """Woodbury-updated sweep handle vs re-eigh, as pending rank grows."""
    from repro.core.engine import AnalyticEngine

    rng = np.random.default_rng(2)
    eng = AnalyticEngine("numpy_f64", gamma=1.0)
    x = rng.standard_normal((4 * d, d))
    y = np.eye(c)[rng.integers(0, c, 4 * d)]
    stats = eng.client_stats(x, y)
    gammas = list(np.logspace(-2, 1, n_gammas))
    handle0 = eng.sweep_factor(stats)

    rows = []
    for k in ranks:
        u = rng.standard_normal((k, d))
        stats_k = eng.merge(stats, eng.client_stats(
            u, np.eye(c)[rng.integers(0, c, k)]))
        handle = handle0.rank_update(u) if k else handle0

        def woodbury():
            eng.sweep_solve(handle, stats_k.moment, gammas)

        def re_eigh():
            eng.sweep_solve(eng.sweep_factor(stats_k), stats_k.moment,
                            gammas)

        t_w = _time(woodbury, repeat)
        t_e = _time(re_eigh, repeat)
        rows.append(dict(bench="sweep_handle", d=d, n_gammas=n_gammas,
                         pending_rank=k, woodbury_s=t_w, re_eigh_s=t_e,
                         speedup=t_e / t_w))
    return rows


_TILED_SUBPROC_FLAG = "--tiled-subprocess"
_DIST_SUBPROC_FLAG = "--dist-subprocess"

# Per-device transient budget for the gather-then-factor baseline: a shard
# whose solve transiently materializes the full (d, d) f64 system must fit
# it next to the resident tile, the model weights, and XLA's workspace.
# 256 MiB is the d≈5792 line — d=6144 (302 MiB) and d=8192 (512 MiB) are
# where gather-then-factor stops being runnable per device and only the
# tile-parallel factor (peak d²/shards) proceeds.
DEVICE_TRANSIENT_BUDGET = 256 * 2**20


def _dist_subprocess_main(d: int, run_baseline: bool) -> None:
    """x64 / 8-device child: tile-parallel distributed factor vs the
    gather-then-factor baseline at dimension d, with static peak-transient
    accounting (the no-(d,d)-anywhere acceptance invariant)."""
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["JAX_ENABLE_X64"] = "1"
    os.environ["JAX_DEFAULT_DTYPE_BITS"] = "32"
    import numpy as np

    import jax.numpy as jnp

    from repro.core.distributed import (federation_mesh,
                                        make_tiled_federated_solve)
    from repro.launch.hlo_analysis import peak_aval_bytes

    n, c = 8, 16
    r = d // n
    rng = np.random.default_rng(0)
    # full-rank SPD aggregate built tile-by-tile (diagonal + rank-32), so
    # the STAGE never allocates a (d, d) either — only the host parity
    # reference below does, and only because numpy is the oracle
    u = rng.standard_normal((d, 32))
    diag = 1.0 + rng.random(d) * d
    q = rng.standard_normal((d, c))
    tiles = []
    for i in range(n):
        t = u[i * r:(i + 1) * r] @ u.T
        t[np.arange(r), i * r + np.arange(r)] += diag[i * r:(i + 1) * r]
        tiles.append(t)
    gt = jnp.asarray(np.stack(tiles))
    mt = jnp.asarray(np.stack([q[i * r:(i + 1) * r] for i in range(n)]))
    mesh = federation_mesh(n)

    fn_dist = make_tiled_federated_solve(
        mesh, target_gamma=0.5, distributed_factor=True, dim=d)
    peak_dist, peak_dist_shape = peak_aval_bytes(fn_dist, gt, mt)
    full_bytes = d * d * 8
    # the acceptance invariant, asserted where the numbers are recorded
    assert peak_dist < full_bytes, (
        f"distributed factor materialized a full-system transient: "
        f"{peak_dist_shape}")
    assert peak_dist <= r * d * 8, peak_dist_shape

    t0 = time.perf_counter()
    w_dist = np.asarray(fn_dist(gt, mt))
    t_first = time.perf_counter() - t0
    t0 = time.perf_counter()
    w_dist = np.asarray(fn_dist(gt, mt))
    t_dist = time.perf_counter() - t0

    g_full = np.concatenate(tiles, 0)
    g_full[np.arange(d), np.arange(d)] += 0.5
    ref = np.linalg.solve(g_full, q)
    err = float(np.abs(w_dist - ref).max() / np.abs(ref).max())

    row = dict(
        bench="distributed_factor", d=d, shards=n,
        dist_first_s=t_first, dist_s=t_dist,
        peak_transient_bytes_dist=int(peak_dist),
        peak_transient_shape_dist=peak_dist_shape,
        tile_resident_bytes=int(r * d * 8),
        full_system_bytes=int(full_bytes),
        budget_bytes=int(DEVICE_TRANSIENT_BUDGET),
        baseline_feasible=bool(run_baseline),
        rel_err_vs_numpy_f64=err, parity_1e10=bool(err < 1e-10),
        # whole-resident Mosaic kernel needs the f32 system in VMEM (~16 MB)
        vmem_native_monolithic_ok=bool(d * d * 4 <= 16 * 2**20),
        base_s=None, base_first_s=None, peak_transient_bytes_base=None,
        speedup_vs_gather=None,
    )
    if run_baseline:
        fn_base = make_tiled_federated_solve(mesh, target_gamma=0.5, dim=d)
        peak_base, _ = peak_aval_bytes(fn_base, gt, mt)
        t0 = time.perf_counter()
        w_base = np.asarray(fn_base(gt, mt))
        row["base_first_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        w_base = np.asarray(fn_base(gt, mt))
        row["base_s"] = time.perf_counter() - t0
        row["peak_transient_bytes_base"] = int(peak_base)
        row["speedup_vs_gather"] = row["base_s"] / row["dist_s"]
        assert peak_base >= full_bytes      # the baseline DOES gather
        err_b = float(np.abs(w_base - ref).max() / np.abs(ref).max())
        row["base_rel_err_vs_numpy_f64"] = err_b
    print(json.dumps(row))


def _cpu_child(*args: str) -> dict:
    """Run one measurement of this module in a fresh child pinned to the
    CPU (8 virtual devices, x64 — process-global knobs), so a parent that
    holds a chip never waits on a child that wants it. Its row is labelled
    ``platform="cpu"``: these are CPU walls, not device numbers."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # child needs repro (src) AND the benchmarks package (root) on its path
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), root] +
        ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    res = subprocess.run(
        [sys.executable, os.path.abspath(__file__), *args],
        capture_output=True, text=True, env=env, cwd=root)
    if res.returncode != 0:
        raise RuntimeError(f"{args[0]} child failed:\n{res.stderr}")
    return dict(json.loads(res.stdout.strip().splitlines()[-1]),
                platform="cpu")


def bench_distributed_factor(d: int):
    """One distributed-factor measurement in a CPU child; the
    gather-then-factor baseline runs only where its (d, d) per-device
    transient fits the budget."""
    run_baseline = d * d * 8 <= DEVICE_TRANSIENT_BUDGET
    return _cpu_child(_DIST_SUBPROC_FLAG, str(d), str(int(run_baseline)))


def _tiled_subprocess_main(d: int) -> None:
    """Runs inside the x64 / 8-device child: tiled vs sync at dimension d."""
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["JAX_ENABLE_X64"] = "1"
    import numpy as np

    from repro.core.engine import AnalyticEngine, SuffStats
    from repro.fl import ShardedCoordinator

    c = 100
    rng = np.random.default_rng(0)
    # a cheap full-rank SPD aggregate at d=6144 scale: diagonal + low rank
    # (a dense X of 4·d rows would cost a 463-GFlop host matmul just to
    # set the stage)
    u = rng.standard_normal((d, 64))
    gram = u @ u.T + np.diag(1.0 + rng.random(d) * d)
    q = rng.standard_normal((d, c))

    eng = AnalyticEngine("numpy_f64", gamma=1.0)
    stats = SuffStats(gram=gram, moment=q, count=float(d), clients=8.0)

    t0 = time.perf_counter()
    w_sync = eng.solve(stats, target_gamma=0.5)
    t_sync = time.perf_counter() - t0

    coord = ShardedCoordinator(d, c, gamma=1.0, tiled_gram=True)
    n = coord.num_shards
    r = d // n
    coord._gram_tiles = [gram[i * r:(i + 1) * r].copy() for i in range(n)]
    coord._moment_tiles = [q[i * r:(i + 1) * r].copy() for i in range(n)]
    coord._count = float(d)
    coord._seen = set(range(8))
    t0 = time.perf_counter()
    w_tiled = coord.solve(0.5)
    t_first = time.perf_counter() - t0                  # includes compile
    t0 = time.perf_counter()
    w_tiled = coord.solve(0.5)
    t_tiled = time.perf_counter() - t0

    err = float(np.abs(w_tiled - w_sync).max())
    print(json.dumps(dict(
        bench="tiled_6144", d=d, shards=n,
        sync_solve_s=t_sync, tiled_solve_s=t_tiled,
        tiled_first_solve_s=t_first,
        max_abs_err_vs_sync=err, parity_1e6=bool(err < 1e-6),
        resident_bytes_per_shard_tiled=int(r * d * 8),
        resident_bytes_per_shard_leaf=int(d * d * 8),
    )))


def bench_tiled(d: int):
    return _cpu_child(_TILED_SUBPROC_FLAG, str(d))


def run(quick: bool = False) -> list[dict]:
    out = []

    d, c, ng = (512, 20, 8) if quick else (2048, 100, 16)
    row = bench_fused_sweep(d, c, ng)
    out.append(row)
    print_table(
        "Fused multi-γ sweep (Pallas, interpret on CPU) vs host paths",
        ["case", "per-γ loop s", "eigh sweep s", "fused s", "vs loop",
         "vs eigh", "rel err"],
        [[f"d={d} C={c} |γ|={ng}", f"{row['host_loop_s']:.2f}",
          f"{row['eigh_sweep_s']:.2f}", f"{row['fused_s']:.2f}",
          f"{row['speedup_vs_loop']:.2f}x",
          f"{row['speedup_vs_eigh']:.2f}x",
          f"{row['fused_rel_err']:.1e}"]])

    d2, batch = (256, 4) if quick else (1024, 8)
    row = bench_batched_factor(d2, 16, batch)
    out.append(row)
    print_table(
        "Batched blocked Cholesky + substitution vs numpy loop",
        ["case", "numpy s", "kernel s", "speedup"],
        [[f"d={d2} batch={batch}", f"{row['host_s']:.2f}",
          f"{row['kernel_s']:.2f}", f"{row['speedup']:.2f}x"]])

    d3 = 256 if quick else 1024
    ranks = [0, d3 // 64, d3 // 16, d3 // 8, d3 // 4]
    rows = bench_sweep_handle(d3, 16, 8 if quick else 16, ranks)
    out.extend(rows)
    print_table(
        "Repeated sweeps on an evolving federation: Woodbury handle vs "
        "re-eigh",
        ["pending rank", "woodbury s", "re-eigh s", "speedup"],
        [[r["pending_rank"], f"{r['woodbury_s']:.3f}",
          f"{r['re_eigh_s']:.3f}", f"{r['speedup']:.1f}x"] for r in rows])

    d4 = 768 if quick else 6144
    row = bench_tiled(d4)
    out.append(row)
    print_table(
        "Tiled-Gram ShardedCoordinator, 8-way CPU mesh, x64 subprocess",
        ["case", "sync s", "tiled s", "max |Δ| vs sync", "tile MB/shard",
         "leaf MB/shard"],
        [[f"d={d4}", f"{row['sync_solve_s']:.2f}",
          f"{row['tiled_solve_s']:.2f}",
          f"{row['max_abs_err_vs_sync']:.1e}",
          f"{row['resident_bytes_per_shard_tiled'] / 2**20:.0f}",
          f"{row['resident_bytes_per_shard_leaf'] / 2**20:.0f}"]])
    if not row["parity_1e6"]:
        raise AssertionError(
            f"tiled-vs-sync parity exceeded 1e-6: {row['max_abs_err_vs_sync']}")

    ds = [256, 512] if quick else [2048, 4096, 6144, 8192]
    dist_rows = [bench_distributed_factor(d) for d in ds]
    out.extend(dist_rows)
    print_table(
        "Tile-parallel distributed factor vs gather-then-factor, 8-way "
        "CPU mesh, x64 subprocess per d",
        ["d", "dist s", "gather s", "speedup", "peak MB dist",
         "peak MB gather", "budget MB", "rel err"],
        [[r["d"], f"{r['dist_s']:.2f}",
          "infeasible" if r["base_s"] is None else f"{r['base_s']:.2f}",
          "—" if r["speedup_vs_gather"] is None
          else f"{r['speedup_vs_gather']:.2f}x",
          f"{r['peak_transient_bytes_dist'] / 2**20:.0f}",
          "—" if r["peak_transient_bytes_base"] is None
          else f"{r['peak_transient_bytes_base'] / 2**20:.0f}",
          f"{r['budget_bytes'] / 2**20:.0f}",
          f"{r['rel_err_vs_numpy_f64']:.1e}"] for r in dist_rows])
    bad = [r["d"] for r in dist_rows if not r["parity_1e10"]]
    if bad:
        raise AssertionError(
            f"distributed-factor parity exceeded 1e-10 at d={bad}")
    return out


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == _TILED_SUBPROC_FLAG:
        _tiled_subprocess_main(int(sys.argv[2]))
        sys.exit(0)
    if len(sys.argv) >= 4 and sys.argv[1] == _DIST_SUBPROC_FLAG:
        _dist_subprocess_main(int(sys.argv[2]), bool(int(sys.argv[3])))
        sys.exit(0)
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-scale sizes (same as run.py --quick)")
    args = ap.parse_args()
    rows = run(quick=args.smoke)
    if not args.smoke:
        outdir = os.path.join("results", "bench")
        os.makedirs(outdir, exist_ok=True)
        with open(os.path.join(outdir, "solve_kernels_bench.json"),
                  "w") as fh:
            json.dump(rows, fh, indent=1)
