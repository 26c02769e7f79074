#!/usr/bin/env python3
"""Chip smoke test: the AFL main path on a TPU, through its normal entry points.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # the sharded paths on four chips

One chip runs two phases:

  * train — ``repro.launch.train.run_analytic`` on ``xlstm_350m`` at its
    published widths (24 layers, d_model 1024, random weights from a seed)
    with the Pallas Gram kernel: frozen-backbone forward → Gram fold → one
    report → aggregation → head. Checks that the kernel ran compiled, that
    the folded Gram matches ``XᵀX`` from ``jax.numpy`` at
    ``precision="highest"`` on the same embeddings, and that the head solves
    the centralized f64 problem of ``fl/afl.py::joint_ridge``.
  * engine — ``AnalyticEngine("jax", use_kernel=True)`` at d=1024 against
    ``numpy_f64``: factor, solve, the fused multi-γ sweep and the rank-k
    factor update.

``--chips 4`` runs only what exists across chips: ``ShardedCoordinator`` at
d=1024 (the plain psum solve, and the tiled Gram with the distributed
factor) against an ``AFLServer`` oracle on the same reports, and
``run_analytic`` on the (4, 1) host mesh against the one-chip run (both
with the backbone at ``precision="highest"``): embeddings, report
statistics and head.

Each check prints its value beside its limit. The last line of standard
output is one JSON object, ``{"ok": true, "device": {...}}``, printed only
when every check passed. Without a TPU the script exits non-zero and prints
no result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

SEED = 0
BATCH, SEQ = 32, 128            # one client batch of token sequences
TRAIN_BATCHES, TEST_BATCHES = 36, 4
ENGINE_DIM, ENGINE_ROWS, ENGINE_CLASSES = 1024, 4096, 16
RANK_K = 8                      # rows folded by the rank-k update

# Tolerances. u32 = 2^-24 is the f32 unit roundoff.
#
# Gram: the folded Gram sums N = 1152 rows in f32; the worst-case
# accumulation error relative to max|G| is N·u32 ≈ 6.9e-5. One bf16 MXU pass
# instead rounds each operand to 8 mantissa bits, an error near 2^-9 ≈ 2e-3.
# The limit sits between the two.
GRAM_TOL = 1e-4
# Head: the embeddings of a random-weight backbone are ill-conditioned and
# the head is the γ=0 (RI-restored) solve, so the forward difference from
# joint_ridge scales with κ(XᵀX) and is reported, not gated. What the AA law
# guarantees is that the head solves the centralized normal equations
# G W = Q; its normwise backward error there, ‖GW − Q‖ / (‖G‖‖W‖ + ‖Q‖),
# is bounded by the relative error of the f32 statistics it was solved
# from — the Gram bound above.
HEAD_BACKWARD_TOL = 1e-4
# Device solves at d=1024 in f32 against host f64: the systems are built
# with κ ≤ 10, and a backward-stable f32 factor or solve then errs by at
# most about κ·d·u32 ≈ 6e-4 relative. One bf16 pass would give κ·2^-9 ≈ 2e-2.
SOLVE_TOL = 1e-3
ENGINE_GAMMA = 0.1              # ridge of the engine-phase systems (κ ≈ 7)


class Checks:
    """Prints each measured value beside its limit and remembers failures."""

    def __init__(self):
        self.failed = []

    def le(self, name: str, value: float, limit: float) -> None:
        ok = bool(np.isfinite(value)) and value <= limit
        print(f"  check {name}: {value:.3e} <= {limit:.1e}  "
              f"{'ok' if ok else 'FAIL'}", flush=True)
        if not ok:
            self.failed.append(name)

    def true(self, name: str, ok: bool, detail: str = "") -> None:
        print(f"  check {name}: {detail}  {'ok' if ok else 'FAIL'}",
              flush=True)
        if not ok:
            self.failed.append(name)


def _rel(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _timed(fn, *args):
    """(result, seconds) of one call, finished on the device."""
    import jax

    t = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t


def _lowers_to_kernel(fn, *args) -> bool:
    import jax

    return "tpu_custom_call" in jax.jit(fn).lower(*args).as_text()


def _token_data(cfg):
    from repro.data import synthetic as D

    n_train, n_test = TRAIN_BATCHES * BATCH, TEST_BATCHES * BATCH
    ds = D.token_classification(n=n_train + n_test, seq=SEQ,
                                vocab=cfg.vocab_size,
                                num_classes=cfg.num_classes, seed=SEED)
    return D.train_test_split(ds, n_test / (n_train + n_test), seed=SEED)


def _embeddings(cfg, mesh, ds):
    """The frozen backbone's embeddings of ``ds``, exactly as run_analytic
    computes them (same seed-0 weights, same jitted forward)."""
    import jax
    import jax.numpy as jnp

    from repro.launch import train as TR
    from repro.models import transformer as T

    params = T.init_params(jax.random.key(0), cfg)
    embed = TR._embed_fn(params, cfg, mesh)
    batches = list(TR._batches(ds, BATCH))
    first, setup_s = _timed(embed, params, jnp.asarray(batches[0][0]))
    t = time.perf_counter()
    rest = [embed(params, jnp.asarray(tb)) for tb, _ in batches[1:]]
    rest = jax.block_until_ready(rest)
    steady_s = time.perf_counter() - t
    x = jnp.concatenate([first] + rest)
    labels = np.concatenate([lb for _, lb in batches])
    per = steady_s / max(len(rest), 1)
    print(f"  backbone forward: compile+first batch {setup_s:.2f}s, steady "
          f"{per * 1e3:.1f} ms/batch ({BATCH}x{SEQ} tokens, "
          f"{len(batches)} batches)", flush=True)
    return x, labels


def _backward_error(g, q, w) -> float:
    return float(np.linalg.norm(g @ w - q)
                 / (np.linalg.norm(g) * np.linalg.norm(w) + np.linalg.norm(q)))


def phase_train(cfg, mesh, checks: Checks):
    """run_analytic at full width with the Gram kernel."""
    import jax
    import jax.numpy as jnp

    from repro.config import FLConfig
    from repro.data import synthetic as D
    from repro.fl.afl import joint_ridge
    from repro.kernels import ops
    from repro.launch import train as TR

    print(f"[train] {cfg.name}: {cfg.num_layers} layers, d_model "
          f"{cfg.d_model}, vocab {cfg.vocab_size}, head {cfg.num_classes} "
          f"classes, mesh {dict(mesh.shape)}", flush=True)
    t = time.perf_counter()
    train_ds, test_ds = _token_data(cfg)
    print(f"  token data: {len(train_ds)} train / {len(test_ds)} test "
          f"sequences in {time.perf_counter() - t:.2f}s", flush=True)
    x, labels = _embeddings(cfg, mesh, train_ds)
    y = jax.nn.one_hot(jnp.asarray(labels), cfg.num_classes)

    checks.true("gram kernel compiled", not ops.interpret_default(),
                f"interpret_default()={ops.interpret_default()}")
    checks.true("gram kernel lowers to tpu_custom_call",
                _lowers_to_kernel(ops.gram_update, x[:BATCH], y[:BATCH]))

    run = TR.run_analytic(cfg, mesh, train_ds, test_ds, FLConfig(gamma=1.0),
                          BATCH, use_kernel=True)
    print(f"  run_analytic: {run.train_seconds:.2f}s for forward + Gram fold "
          f"+ report + solve over {len(labels)} sequences (compiled "
          f"programs from the persistent cache); test acc "
          f"{run.accuracy:.4f}", flush=True)

    g_ref = np.asarray(jnp.dot(x.T, x, precision="highest"), np.float64)
    g_run, _ = _report_system(run)
    gram_err = float(np.max(np.abs(g_run - g_ref)) / np.max(np.abs(g_ref)))
    checks.le("folded Gram vs jnp XᵀX (max rel)", gram_err, GRAM_TOL)

    x64 = np.asarray(x, np.float64)
    feats = D.Dataset(x64, labels, cfg.num_classes)
    w_ref, _ = joint_ridge(feats, feats, gamma=0.0)
    y64 = np.eye(cfg.num_classes)[labels]
    g64, q64 = x64.T @ x64, x64.T @ y64
    eig = np.linalg.eigvalsh(g64)
    kappa = float(eig[-1] / max(eig[0], eig[-1] * 1e-300))
    print(f"  κ(XᵀX) = {kappa:.3e}; head vs joint_ridge forward rel diff "
          f"{_rel(run.head, w_ref):.3e} (joint_ridge's own backward error "
          f"{_backward_error(g64, q64, w_ref):.3e})", flush=True)
    checks.le("head backward error on joint_ridge's system",
              _backward_error(g64, q64, run.head), HEAD_BACKWARD_TOL)


def _engine_system(rng):
    """A d=1024 SPD system with κ ≤ 10: a Gram of Gaussian rows over N = 4d
    (eigenvalues in [(1−½)², (1+½)²]) plus ENGINE_GAMMA."""
    x = rng.standard_normal((ENGINE_ROWS, ENGINE_DIM)) / np.sqrt(ENGINE_ROWS)
    y = rng.standard_normal((ENGINE_ROWS, ENGINE_CLASSES))
    return x, y


def phase_engine(checks: Checks):
    import jax.numpy as jnp

    from repro.core.engine import AnalyticEngine, SuffStats
    from repro.kernels import ops

    print(f"[engine] AnalyticEngine('jax', use_kernel=True) vs numpy_f64 at "
          f"d={ENGINE_DIM}, C={ENGINE_CLASSES}", flush=True)
    rng = np.random.default_rng(SEED)
    x, y = _engine_system(rng)
    host = AnalyticEngine("numpy_f64", gamma=1.0)
    dev = AnalyticEngine("jax", gamma=1.0, use_kernel=True)
    s_host = host.client_stats(x, y)
    # the device gets the same statistics, rounded to f32 once
    s_dev = SuffStats(*(jnp.asarray(np.asarray(v, np.float32))
                        for v in s_host[:4]))
    a = s_host.gram + ENGINE_GAMMA * np.eye(ENGINE_DIM)
    a32 = jnp.asarray(a, jnp.float32)

    for name, fn, args in (
            ("blocked_cholesky", ops.blocked_cholesky, (a32[None],)),
            ("multi_gamma_solve", ops.multi_gamma_solve,
             (s_dev.gram, s_dev.moment, jnp.ones((4,), jnp.float32))),
            ("chol_rank_update", ops.chol_rank_update,
             (a32, a32[:RANK_K]))):
        checks.true(f"{name} lowers to tpu_custom_call",
                    _lowers_to_kernel(fn, *args))

    factor = lambda: dev.factor(s_dev, target_gamma=ENGINE_GAMMA).handle[0]
    l_dev, t_first = _timed(factor)
    l_dev, t_steady = _timed(factor)
    print(f"  factor: first {t_first:.3f}s, steady {t_steady * 1e3:.2f} ms",
          flush=True)
    checks.le("factor L vs numpy cholesky (rel)",
              _rel(l_dev, np.linalg.cholesky(a)), SOLVE_TOL)

    f = dev.factor(s_dev, target_gamma=ENGINE_GAMMA)
    solve = lambda: dev.factor_solve(f, s_dev.moment)
    w_dev, t_first = _timed(solve)
    w_dev, t_steady = _timed(solve)
    print(f"  solve: first {t_first:.3f}s, steady {t_steady * 1e3:.2f} ms",
          flush=True)
    checks.le("solve vs numpy_f64 (rel)",
              _rel(w_dev, host.solve(s_host, target_gamma=ENGINE_GAMMA)),
              SOLVE_TOL)

    gammas = [ENGINE_GAMMA, 0.3, 1.0, 3.0, 10.0, 30.0, 100.0, 300.0]
    sweep = lambda: dev.backend.fused_sweep(s_dev.gram, s_dev.moment, gammas)
    ws_dev, t_first = _timed(sweep)
    ws_dev, t_steady = _timed(sweep)
    print(f"  multi-γ sweep ({len(gammas)} γ): first {t_first:.3f}s, steady "
          f"{t_steady * 1e3:.2f} ms", flush=True)
    ws_host = host.solve_multi_gamma(s_host, gammas)
    checks.le("multi-γ sweep vs numpy_f64 (max rel over γ)",
              max(_rel(w, r) for w, r in zip(ws_dev, ws_host)), SOLVE_TOL)

    xs = rng.standard_normal((RANK_K, ENGINE_DIM)) / np.sqrt(ENGINE_ROWS)
    update = lambda: f.rank_update(jnp.asarray(xs, jnp.float32)).handle[0]
    l_up, t_first = _timed(update)
    l_up, t_steady = _timed(update)
    print(f"  rank-{RANK_K} update: first {t_first:.3f}s, steady "
          f"{t_steady * 1e3:.2f} ms", flush=True)
    checks.le("rank-k update vs numpy cholesky (rel)",
              _rel(l_up, np.linalg.cholesky(a + xs.T @ xs)), SOLVE_TOL)


def _collectives(compiled_text: str) -> dict:
    return {op: compiled_text.count(op + "(")
            for op in ("all-reduce", "all-gather", "reduce-scatter",
                       "collective-permute")
            if op + "(" in compiled_text}


def phase_sharded(checks: Checks, n_dev: int):
    """ShardedCoordinator (plain psum, and tiled + distributed factor) at
    d=1024 against an AFLServer oracle on the same reports."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.distributed import (federation_mesh, make_federated_solve,
                                        make_tiled_federated_solve)
    from repro.core.streaming import AnalyticState
    from repro.fl.api import AFLServer, ShardedCoordinator, make_report

    d, c = ENGINE_DIM, ENGINE_CLASSES
    print(f"[sharded] ShardedCoordinator on {n_dev} devices vs AFLServer "
          f"at d={d}", flush=True)
    rng = np.random.default_rng(SEED)
    x, y = _engine_system(rng)
    parts = np.array_split(np.arange(len(x)), 2 * n_dev)
    reports = [make_report(i, x[p], y[p], 1.0) for i, p in enumerate(parts)]
    oracle = AFLServer(d, c, gamma=1.0)
    oracle.submit_many(reports)
    w_ref = oracle.solve(target_gamma=ENGINE_GAMMA)

    mesh = federation_mesh(n_dev)
    for tiled in (False, True):
        coord = ShardedCoordinator(d, c, gamma=1.0, mesh=mesh,
                                   tiled_gram=tiled)
        coord.submit_many(reports)
        w, t_first = _timed(lambda: coord.solve(target_gamma=ENGINE_GAMMA))
        w, t_steady = _timed(lambda: coord.solve(target_gamma=ENGINE_GAMMA))
        kind = "tiled+distributed factor" if tiled else "plain psum"
        print(f"  {kind}: first solve {t_first:.3f}s, steady "
              f"{t_steady * 1e3:.2f} ms", flush=True)
        checks.le(f"{kind} vs AFLServer (rel)", _rel(w, w_ref), SOLVE_TOL)

    # the same programs, lowered over the mesh: one shard per device, and
    # the collectives that join them
    row = NamedSharding(mesh, P("data"))
    rows = d // n_dev
    tiles = jax.device_put(np.zeros((n_dev, rows, d), np.float32), row)
    checks.true("gram tiles span the mesh", len(tiles.devices()) == n_dev,
                f"{len(tiles.devices())} devices, shard "
                f"{tiles.addressable_shards[0].data.shape}")
    spec = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                              sharding=row)
    tiled_fn = make_tiled_federated_solve(mesh, target_gamma=ENGINE_GAMMA,
                                          distributed_factor=True, dim=d)
    txt = tiled_fn.lower(spec((n_dev, rows, d)),
                         spec((n_dev, rows, c))).compile().as_text()
    coll = _collectives(txt)
    checks.true("tiled solve: all-gather + all-reduce across the mesh",
                coll.get("all-gather", 0) > 0 and coll.get("all-reduce", 0) > 0
                and "tpu_custom_call" in txt, f"{coll}")
    plain_fn = make_federated_solve(mesh, target_gamma=ENGINE_GAMMA)
    state = AnalyticState(spec((n_dev, d, d)), spec((n_dev, d, c)),
                          spec((n_dev,)))
    coll = _collectives(plain_fn.lower(state).compile().as_text())
    checks.true("plain solve: one psum across the mesh",
                coll.get("all-reduce", 0) > 0, f"{coll}")


def _report_system(run):
    """The f64 normal equations (XᵀX, XᵀY) a run's report carries."""
    g = run.report.gram - run.report.gamma * np.eye(run.report.gram.shape[0])
    return np.asarray(g, np.float64), np.asarray(run.report.moment, np.float64)


def _embed_timed(cfg, mesh, tokens):
    """One batch of embeddings on ``mesh``, its steady seconds, and the
    compiled forward's temporary bytes per device."""
    import jax

    from repro.launch import train as TR
    from repro.models import transformer as T

    params = T.init_params(jax.random.key(0), cfg)
    embed = TR._embed_fn(params, cfg, mesh).lower(params, tokens).compile()
    emb, _ = _timed(embed, params, tokens)
    _, steady = _timed(embed, params, tokens)
    return emb, steady, embed.memory_analysis().temp_size_in_bytes


def phase_train_mesh(cfg, checks: Checks, n_dev: int):
    """run_analytic on the (n, 1) host mesh against the one-chip head.

    The two runs' embeddings are not bit-identical on a TPU: the programs
    for the two partitions of the batch round differently, and 24
    random-weight recurrent layers carry that through. The gate is
    therefore the head's backward error on the one-chip normal equations
    (HEAD_BACKWARD_TOL), with the embedding and statistics differences
    printed beside it. Both runs trace the backbone at
    ``precision="highest"``: at the TPU's default, one bf16 pass per f32
    matmul, the partitions differed enough for a 6.7e-3 backward error on a
    v5e, against 6.6e-6 at highest.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.config import FLConfig
    from repro.fl.api import ShardedCoordinator
    from repro.kernels import ops
    from repro.launch import mesh as M
    from repro.launch import train as TR

    one = M.auto_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    host_mesh = M.make_host_mesh()
    checks.true("host mesh spans every chip",
                host_mesh.devices.size == n_dev, f"{dict(host_mesh.shape)}")
    print(f"[train-mesh] {cfg.name} run_analytic, one chip vs "
          f"{dict(host_mesh.shape)}, backbone at precision=highest",
          flush=True)

    # rows sharded over the mesh fold where they live: the kernel on each
    # chip, one psum
    rng = np.random.default_rng(SEED)
    xr = rng.standard_normal((n_dev * BATCH, cfg.d_model)).astype(np.float32)
    yr = np.eye(cfg.num_classes, dtype=np.float32)[
        rng.integers(0, cfg.num_classes, n_dev * BATCH)]
    rows = NamedSharding(host_mesh, P("data"))
    g_mesh, _ = ops.gram_update(jax.device_put(xr, rows),
                                jax.device_put(yr, rows))
    g_one, _ = ops.gram_update(jax.numpy.asarray(xr), jax.numpy.asarray(yr))
    checks.true("sharded Gram fold ran on every chip",
                len(g_mesh.sharding.device_set) == n_dev,
                f"result on {len(g_mesh.sharding.device_set)} devices")
    checks.le("sharded Gram fold vs one-chip fold (max rel)",
              float(np.max(np.abs(np.asarray(g_mesh) - np.asarray(g_one)))
                    / np.max(np.abs(np.asarray(g_one)))), GRAM_TOL)

    train_ds, test_ds = _token_data(cfg)
    tokens = jnp.asarray(train_ds.x[:BATCH])
    fl = FLConfig(gamma=1.0)
    with jax.default_matmul_precision("highest"):
        run_1 = TR.run_analytic(cfg, one, train_ds, test_ds, fl, BATCH,
                                use_kernel=True)
        run_n = TR.run_analytic(cfg, host_mesh, train_ds, test_ds, fl, BATCH,
                                use_kernel=True)
        emb_1, fwd_1, tmp_1 = _embed_timed(cfg, one, tokens)
        emb_n, fwd_n, tmp_n = _embed_timed(cfg, host_mesh, tokens)
    print(f"  run_analytic: one chip {run_1.train_seconds:.2f}s, acc "
          f"{run_1.accuracy:.4f}; {dict(host_mesh.shape)} "
          f"{run_n.train_seconds:.2f}s, acc {run_n.accuracy:.4f}", flush=True)
    print(f"  forward, one batch: one chip {fwd_1 * 1e3:.1f} ms, "
          f"{tmp_1 / 2**20:.0f} MiB temp; mesh {fwd_n * 1e3:.1f} ms, "
          f"{tmp_n / 2**20:.0f} MiB temp per device", flush=True)
    checks.true("mesh forward's embeddings span every chip",
                len(emb_n.sharding.device_set) == n_dev,
                f"{len(emb_n.sharding.device_set)} devices, spec "
                f"{getattr(emb_n.sharding, 'spec', None)}")
    g1, q1 = _report_system(run_1)
    gn, qn = _report_system(run_n)
    print(f"  mesh vs one chip (rel): first-batch embeddings "
          f"{_rel(emb_n, emb_1):.3e}, report Gram {_rel(gn, g1):.3e}, "
          f"report XᵀY {_rel(qn, q1):.3e}, head "
          f"{_rel(run_n.head, run_1.head):.3e}", flush=True)
    checks.le(f"{n_dev}-chip head backward error on its own report",
              _backward_error(gn, qn, run_n.head), HEAD_BACKWARD_TOL)
    checks.le(f"{n_dev}-chip head backward error on the one-chip system",
              _backward_error(g1, q1, run_n.head), HEAD_BACKWARD_TOL)
    # the same report through the sharded solve at default precision
    coord = ShardedCoordinator(cfg.d_model, cfg.num_classes, gamma=fl.gamma,
                               mesh=host_mesh, axis_names=M.batch_axes(
                                   host_mesh))
    coord.submit(run_n.report)
    checks.le(f"{n_dev}-chip solve at default precision, backward error",
              _backward_error(gn, qn, coord.solve(target_gamma=0.0)),
              HEAD_BACKWARD_TOL)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded paths, across four chips")
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{devices[0].platform!r}); this check runs only on a chip",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, "
              f"JAX sees {len(devices)}", file=sys.stderr)
        return 2

    from repro.configs.registry import get_config
    from repro.launch import mesh as M
    from repro.launch.train import use_compile_cache

    use_compile_cache()
    kind = devices[0].device_kind
    print(f"device: {kind} x{len(devices)} (platform tpu), jax "
          f"{jax.__version__}; compile cache "
          f"{jax.config.jax_compilation_cache_dir}", flush=True)
    cfg = get_config("xlstm_350m")
    checks = Checks()
    t0 = time.perf_counter()
    if args.chips == 1:
        one = M.auto_mesh((1, 1), ("data", "model"), devices=devices[:1])
        phase_train(cfg, one, checks)
        phase_engine(checks)
    else:
        phase_sharded(checks, args.chips)
        phase_train_mesh(cfg, checks, args.chips)
    print(f"total {time.perf_counter() - t0:.1f}s", flush=True)
    if checks.failed:
        print(f"chip_smoke: FAILED: {', '.join(checks.failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
