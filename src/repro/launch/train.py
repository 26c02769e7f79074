"""Training launcher: AFL analytic training of a backbone+head, end to end.

Runs the paper's pipeline on real devices (the host mesh on CPU; the
production mesh on TPU): frozen-backbone forward → streaming Gram statistics
per federation shard → ONE ``federated_solve`` collective → linear head.
Optionally runs the gradient-FL baseline (head SGD + periodic averaging) on
the same data for comparison, and a full-backbone LM pre-training mode
(``--mode lm``) for the generic train driver.

Usage (CPU example — reduced config):
  PYTHONPATH=src python -m repro.launch.train --arch qwen3_32b --reduced \
      --samples 2048 --seq 64 --classes 16 --batch 64
"""

from __future__ import annotations

import argparse
import os
import pathlib
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import FLConfig
from repro.configs.registry import get_config
from repro.core import act
from repro.data import synthetic as D
from repro.fl.api import (AFLClient, AFLServer, ClientReport,
                          ShardedCoordinator)
from repro.launch import mesh as M
from repro.launch import sharding as SH
from repro.launch import steps as ST
from repro.launch.inputs import sample_batch
from repro.models import transformer as T
from repro.optim import wsd_schedule


CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def use_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache for an entry point.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    sets nothing. Otherwise the cache lives at ``<checkout>/.jax_cache`` — a
    fixed path, since a cache directory that moves never hits.
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(CHECKOUT / ".jax_cache"))


class AnalyticRun(NamedTuple):
    accuracy: float
    train_seconds: float
    head: np.ndarray          # (d, C) f64, the coordinator's solve
    report: ClientReport      # the client's one upload (its Gram statistics)


def _batches(ds: D.Dataset, batch: int):
    n = (len(ds) // batch) * batch
    for i in range(0, n, batch):
        yield ds.x[i:i + batch], ds.y[i:i + batch]


def _embed_fn(params, cfg, mesh):
    """jitted frozen-backbone embedding: tokens (B,S) → (B,D) f32."""

    def fwd(params, tokens):
        with act.activation_policy(mesh, M.batch_axes(mesh), M.model_axes(mesh)):
            hidden = T.forward(params, cfg, {"tokens": tokens})
            return T.pool(hidden).astype(jnp.float32)

    return jax.jit(fwd)


def run_analytic(cfg, mesh, train_ds, test_ds, fl: FLConfig, batch: int,
                 use_kernel: bool = False,
                 server_url: str = "") -> AnalyticRun:
    """AFL on-device: one epoch of forwards, one aggregation collective.

    Drives the canonical API end to end: an :class:`~repro.fl.api.AFLClient`
    (jax-backend engine; ``use_kernel=True`` folds batches with the Pallas
    Gram kernel) accumulates the local stage, its
    :class:`~repro.fl.api.ClientReport` is submitted to a coordinator —
    a :class:`~repro.fl.service.RemoteCoordinator` when ``server_url``
    points at a live :class:`~repro.fl.service.FederationService` (e.g.
    ``launch/serve.py --federation``), else
    :class:`~repro.fl.api.ShardedCoordinator` when the mesh has >1
    federation shard (one psum collective), plain
    :class:`~repro.fl.api.AFLServer` otherwise.
    """
    params = T.init_params(jax.random.key(0), cfg)
    embed = _embed_fn(params, cfg, mesh)
    client = AFLClient(0, gamma=fl.gamma, backend="jax",
                       use_kernel=use_kernel)
    t0 = time.perf_counter()
    for toks, labels in _batches(train_ds, batch):
        emb = embed(params, jnp.asarray(toks))
        y = jax.nn.one_hot(jnp.asarray(labels), cfg.num_classes)
        client.update(emb, y)
    # single-round aggregation: with >1 devices the sharded coordinator runs
    # the one all-reduce; on one device it degenerates to the plain solve.
    naxes = M.batch_axes(mesh)
    n_shards = 1
    for a in naxes:
        n_shards *= mesh.shape[a]
    if server_url:
        from repro.fl.service import RemoteCoordinator

        coord = RemoteCoordinator(server_url)
        if coord.dim != cfg.d_model:
            raise ValueError(f"remote federation dim={coord.dim} != model "
                             f"d_model={cfg.d_model}")
    elif n_shards > 1:
        coord = ShardedCoordinator(cfg.d_model, cfg.num_classes,
                                   gamma=fl.gamma, mesh=mesh,
                                   axis_names=naxes)
    else:
        coord = AFLServer(cfg.d_model, cfg.num_classes, gamma=fl.gamma)
    report = client.report()
    coord.submit(report)
    w = coord.solve(target_gamma=0.0)
    train_s = time.perf_counter() - t0
    # evaluate
    correct = total = 0
    for toks, labels in _batches(test_ds, batch):
        emb = embed(params, jnp.asarray(toks))
        pred = np.argmax(np.asarray(emb) @ np.asarray(w), -1)
        correct += int((pred == labels).sum())
        total += len(labels)
    return AnalyticRun(float(correct / max(total, 1)), train_s,
                       np.asarray(w, np.float64), report)


def run_gradient(cfg, mesh, train_ds, test_ds, fl: FLConfig, batch: int,
                 rounds: int, lr: float = 0.05):
    """Head-only gradient FL baseline on the same frozen features."""
    params = T.init_params(jax.random.key(0), cfg)
    embed = _embed_fn(params, cfg, mesh)
    step = jax.jit(
        lambda h, e, l: ST.head_sgd_step(h, e, l, lr))
    head = jnp.zeros((cfg.d_model, cfg.num_classes), jnp.float32)
    t0 = time.perf_counter()
    for _ in range(rounds):
        for toks, labels in _batches(train_ds, batch):
            emb = embed(params, jnp.asarray(toks))
            head = step(head, emb, jnp.asarray(labels))
    train_s = time.perf_counter() - t0
    correct = total = 0
    for toks, labels in _batches(test_ds, batch):
        emb = embed(params, jnp.asarray(toks))
        pred = np.argmax(np.asarray(emb) @ np.asarray(head), -1)
        correct += int((pred == labels).sum())
        total += len(labels)
    return float(correct / max(total, 1)), train_s


def run_lm(cfg, mesh, steps: int, batch: int, seq: int, base_lr: float = 3e-3):
    """Generic LM pre-training driver (WSD schedule, minicpm-style)."""
    params = T.init_params(jax.random.key(0), cfg)
    train_step = jax.jit(ST.make_full_train_step(cfg))
    sched = wsd_schedule(base_lr, warmup=max(steps // 10, 1), total=steps)
    losses = []
    for i in range(steps):
        b = sample_batch(cfg, batch, seq, seed=i)
        params, loss = train_step(params, b, sched(i))
        losses.append(float(loss))
    return losses


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--mode", default="analytic",
                    choices=["analytic", "gradient", "lm"])
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--samples", type=int, default=2048)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--classes", type=int, default=16)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--rounds", type=int, default=5, help="gradient-FL rounds")
    ap.add_argument("--steps", type=int, default=50, help="lm steps")
    ap.add_argument("--gamma", type=float, default=1.0)
    ap.add_argument("--kernel", action="store_true",
                    help="fold Gram batches with the Pallas kernel")
    ap.add_argument("--server-url", default="",
                    help="submit to a FederationService at this URL instead "
                         "of aggregating in-process (see launch/serve.py "
                         "--federation)")
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced(num_classes=args.classes)
    mesh = M.make_host_mesh()
    print(f"arch={cfg.name} reduced={args.reduced} mesh={dict(mesh.shape)}")

    if args.mode == "lm":
        losses = run_lm(cfg, mesh, args.steps, args.batch, args.seq)
        print(f"lm: step0 loss={losses[0]:.4f} → step{len(losses)-1} "
              f"loss={losses[-1]:.4f}")
        return

    ds = D.token_classification(
        n=args.samples, seq=args.seq, vocab=cfg.vocab_size,
        num_classes=args.classes, seed=0)
    train_ds, test_ds = D.train_test_split(ds, 0.25, seed=0)
    fl = FLConfig(gamma=args.gamma)
    if args.mode == "analytic":
        acc, dt, _, _ = run_analytic(cfg, mesh, train_ds, test_ds, fl,
                                     args.batch, use_kernel=args.kernel,
                                     server_url=args.server_url)
        where = f" via {args.server_url}" if args.server_url else ""
        print(f"AFL analytic: acc={acc:.4f} train_time={dt:.2f}s (one epoch, "
              f"single aggregation{where})")
    else:
        acc, dt = run_gradient(cfg, mesh, train_ds, test_ds, fl, args.batch,
                               args.rounds)
        print(f"gradient FL baseline: acc={acc:.4f} train_time={dt:.2f}s "
              f"({args.rounds} rounds)")


if __name__ == "__main__":
    main()
