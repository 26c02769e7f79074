"""Driver of ``local`` mixes: one client's local stage, as
``repro.launch.train.run_analytic`` runs it.

Per batch: the jitted frozen-backbone forward with pooling
(``train._embed_fn``), then ``AFLClient(backend="jax",
use_kernel=True).update`` — the Pallas Gram fold (per chip and one psum on
a mesh). Before the window, in set-up, the client folds ``d`` rows of
earlier data (feature rows drawn from the seed), so that the window runs
the steady path of a client with more rows than features: past ``d`` rows
it keeps no host copy of its rows for the low-rank root. The window is a
closed loop over a pool of batches drawn from the seed, with at most
``IN_FLIGHT`` batches dispatched ahead of the device. After it:
``report()`` → ``AFLServer`` (one chip) or ``ShardedCoordinator`` (a mesh)
→ ``solve(target_gamma=0)``, untimed, then the checks against the plain
reference (``bench.check``), after the program's state is freed.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import gc
import sys
import time
import typing

import numpy as np

from bench import check as C
from bench import gen
from bench import precision as PR
from bench import reference as R
from bench import trace
from bench.harness import Check, CompileCounter, Outcome


WARMUP_BATCHES = 2
IN_FLIGHT = 2       # batches dispatched ahead of the device
CHECK_ROWS = 256    # rows of the forward check, spread over every batch


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file: the file's
    keys that name its fields (the others, such as ``family``, are the
    benchmark's); a field whose type is a dataclass is built from its nested
    object in the file (``moe`` → ``MoEConfig``, ``ssm`` → ``SSMConfig``)."""
    from repro.config import ModelConfig

    return _dataclass(ModelConfig, cfg, strict=False)


def _dataclass(cls, values: dict, *, strict: bool):
    """``cls`` built from ``values``, nested dataclasses from nested objects;
    where ``strict``, a key that names no field of ``cls`` is an error."""
    types = typing.get_type_hints(cls)
    fields = [f.name for f in dataclasses.fields(cls)]
    unknown = sorted(set(values) - set(fields))
    if strict and unknown:
        raise ValueError(f"{cls.__name__} has no field {', '.join(unknown)}")
    kw = {}
    for name in fields:
        if name not in values:
            continue
        value, nested = values[name], _dataclass_type(types[name])
        if nested is not None and isinstance(value, dict):
            value = _dataclass(nested, value, strict=True)
        kw[name] = value
    return cls(**kw)


def _dataclass_type(tp):
    """The dataclass a field's type names (``Optional[X]`` → X), or None."""
    for t in (tp, *typing.get_args(tp)):
        if dataclasses.is_dataclass(t):
            return t
    return None


class Phases:
    """Seconds of each phase of a run, for standard error."""

    def __init__(self, t0: float):
        self.last = time.perf_counter()
        self.times = [("start", self.last - t0)]

    def mark(self, name: str) -> None:
        now = time.perf_counter()
        self.times.append((name, now - self.last))
        self.last = now

    def __str__(self) -> str:
        return ", ".join(f"{n} {t:.2f} s" for n, t in self.times)


def _is_gram_kernel(op_name: str) -> bool:
    """The Pallas Gram kernel in a device trace: a ``tpu_custom_call`` named
    after ``gram_update`` (``%gram_update.1 = ... custom-call(...)``)."""
    return "tpu_custom_call" in op_name and "gram" in trace.short(op_name)


def seed_key(seed: int):
    """A JAX key from any whole seed (wider than 32 bits included)."""
    import jax

    seed = int(seed) & (2**64 - 1)
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def _precision(cfg: dict):
    import jax

    if cfg["matmul_precision"] == "default":
        return contextlib.nullcontext()
    return jax.default_matmul_precision(cfg["matmul_precision"])


def _span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def run(cell, devices, *, seed: int, seconds: float, trace_dir, t0: float,
        control: bool = False) -> Outcome:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.fl.api import AFLClient, AFLServer, ShardedCoordinator
    from repro.launch import mesh as M
    from repro.launch import train as TR

    cfg, mix, chips = cell.cfg, cell.mix, cell.chips
    d, c, gamma = cfg["d_model"], cfg["num_classes"], cfg["gamma"]
    batch = mix["batch"]
    if batch % chips:
        raise ValueError(f"batch {batch} does not split over {chips} chips")
    ref = R.of(cfg)
    counter = CompileCounter().install()
    phases = Phases(t0)

    mesh = M.auto_mesh((chips, 1), ("data", "model"), devices=list(devices))
    rows = NamedSharding(mesh, P("data"))
    params = jax.jit(functools.partial(ref.init, cfg),
                     out_shardings=NamedSharding(mesh, P()))(seed_key(seed))
    jax.block_until_ready(params)
    phases.mark("weights")
    tokens, labels = gen.local_pool(mix, cfg, seed)
    onehot = np.eye(c, dtype=np.float32)[labels]
    feed_x = jax.device_put(list(tokens), rows)
    feed_y = jax.device_put(list(onehot), rows)
    pool = len(feed_x)
    pre_x, pre_labels = gen.prefold(mix, cfg, seed)
    pre_y = np.eye(c, dtype=np.float32)[pre_labels]
    phases.mark("pool")

    with _precision(cfg):
        embed = TR._embed_fn(params, program_config(cfg), mesh)
        forward_program = "jit_" + embed.__name__
        client = AFLClient(0, gamma=gamma, backend="jax", use_kernel=True)
        kept = []                   # every embedding the client folded

        def step(i):
            with _span("bench.forward"):
                emb = embed(params, feed_x[i % pool])
            with _span("bench.fold"):
                client.update(emb, feed_y[i % pool])
            kept.append(emb)

        for x, y in zip(pre_x, pre_y):
            client.update(jax.device_put(x, rows), jax.device_put(y, rows))
        # warm-up: compiles (or loads) every program; the second batch
        # folds into statistics that the first made, a new input layout
        for i in range(WARMUP_BATCHES):
            step(i)
        jax.block_until_ready(client._stats)
        setup_s = time.perf_counter() - t0
        phases.mark("warm-up")

        if trace_dir:
            trace.start(trace_dir)
        counter.open = True
        with _span("bench.window"):
            t_start = time.perf_counter()
            ticks = [t_start]   # after each step, to place a slow window
            i = WARMUP_BATCHES
            while True:
                if len(kept) >= IN_FLIGHT:
                    with _span("bench.wait"):
                        kept[-IN_FLIGHT].block_until_ready()
                step(i)
                i += 1
                ticks.append(time.perf_counter())
                if ticks[-1] - t_start >= seconds:
                    break
            with _span("bench.drain"):
                jax.block_until_ready(client._stats)
            t_end = time.perf_counter()
        counter.open = False
        hlo = {}
        if trace_dir:
            jax.profiler.stop_trace()
            # the forward's compiled HLO, which names the scope of each of
            # its operations (the program the window ran: no new compile)
            hlo[forward_program] = embed.lower(
                params, feed_x[0]).compile().as_text()
    phases.mark("window")
    steps = i - WARMUP_BATCHES
    window_s = t_end - t_start
    gaps = np.diff(ticks)
    print(f"bench: {steps} batches in {window_s:.3f} s, setup {setup_s:.3f} s, "
          f"{counter.count} programs compiled inside the window; step "
          f"intervals median {np.median(gaps):.4f} s, longest "
          f"{gaps.max():.4f} s (step {int(gaps.argmax())})"
          + (f"; the pool of {pool} batches wrapped" if steps >= pool else ""),
          file=sys.stderr)
    memory_peak = max((dv.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for dv in devices)

    report = client.report()
    phases.mark("report")
    if chips == 1:
        coord, solved_in = AFLServer(d, c, gamma=gamma), "float64"
    else:
        coord = ShardedCoordinator(d, c, gamma=gamma, mesh=mesh,
                                   axis_names=M.batch_axes(mesh))
        solved_in = "float32"
    coord.submit(report)
    head = np.asarray(coord.solve(target_gamma=0.0), np.float64)
    phases.mark("solve")

    emb_host = [np.asarray(e, np.float32) for e in kept]
    del params, embed, client, coord, kept, feed_x, feed_y
    gc.collect()
    checks = _checks(cell, seed, tokens, onehot, pre_x, pre_y, emb_host,
                     report, head, solved_in, control, devices[0])
    phases.mark("reference checks")
    print(f"bench: {phases}; persistent cache {counter.cache}", file=sys.stderr)

    samples = steps * batch
    facts = {"steps": steps, "samples": samples, "window_s": window_s,
             "samples_per_s": samples / window_s, "rows_per_chip": batch // chips,
             "seq": mix["seq"], "chips": chips,
             "forward_program": forward_program, "hlo": hlo,
             "gram_kernel_match": _is_gram_kernel,
             "compiles_in_window": counter.count}
    return Outcome(
        end_to_end={"local_samples_per_s": samples / window_s,
                    "setup_s": setup_s},
        attempted=steps, failed=0, checks=checks,
        memory_peak_bytes=memory_peak, facts=facts)


def _checks(cell, seed, tokens, onehot, pre_x, pre_y, emb, report, head,
            solved_in, control, device):
    """The four comparisons of ``bench.check`` on what the run folded."""
    import jax
    import jax.numpy as jnp

    cfg, mix, lim = cell.cfg, cell.mix, cell.limits
    ref = R.of(cfg)
    pool, batch = len(tokens), mix["batch"]
    blocks = list(pre_x) + emb
    y_blocks = list(pre_y) + [onehot[i % pool] for i in range(len(emb))]
    rows = np.concatenate(blocks).astype(np.float64)
    ys = np.concatenate(y_blocks)
    raw = report.gram - report.gamma * np.eye(rows.shape[1])

    # forward + pooling, on rows drawn from every batch the client embedded
    picks = gen.check_rows(len(emb), batch, CHECK_ROWS, seed)
    toks = np.stack([tokens[b % pool][r] for b, r in picks])
    with jax.default_device(device):
        params = jax.jit(functools.partial(ref.init, cfg))(seed_key(seed))
        fwd = {k: jax.jit(lambda p, t, k=k: ref.embed(p, cfg, t, k))
               for k in {"highest", PR.control(cfg) if control else "highest"}}

        def embed_rows(kind):
            # in blocks of the window's batch, the last one padded
            pad = -len(toks) % batch
            t = np.concatenate([toks, toks[:pad]])
            return np.concatenate([np.asarray(fwd[kind](
                params, jnp.asarray(t[j:j + batch])))
                for j in range(0, len(t), batch)])[:len(toks)]

        want = embed_rows("highest")
        got = (embed_rows(PR.control(cfg)) if control
               else np.stack([emb[b][r] for b, r in picks]))
        del params, fwd

        # the fold, against float64
        g64, q64 = rows.T @ rows, rows.T @ ys
        if control:
            fold = jax.jit(lambda x, y: (
                PR.einsum("ni,nj->ij", x, x, "high"),
                PR.einsum("ni,nj->ij", x, y, "high")))
            g = jnp.zeros(g64.shape, jnp.float32)
            q = jnp.zeros(q64.shape, jnp.float32)
            for x, y in zip(blocks, y_blocks):
                gi, qi = fold(jnp.asarray(x), jnp.asarray(y))
                g, q = g + gi, q + qi
            g, q = np.asarray(g, np.float64), np.asarray(q, np.float64)
        else:
            g, q = raw, report.moment
    w = C.lower_solve(raw, report.moment, solved_in) if control else head
    return [Check("emb_gap", C.row_gap(got, want), lim["emb_gap"]),
            Check("fold_gap", C.stats_gap(g, q, g64, q64), lim["fold_gap"]),
            Check("count_gap", 0.0 if control else abs(report.count - len(rows)),
                  lim["count_gap"]),
            Check("head_berr", C.backward_error(raw, report.moment, w),
                  lim["head_berr"])]
