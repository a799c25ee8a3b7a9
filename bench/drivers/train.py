"""Training cells: the program's ``Trainer`` on a pool of synthetic
structures, timed over its own epoch loop.

Set-up builds one ``Trainer`` from the configuration file and drives it
from the seed through its first steps, by the same call the window uses:
``Trainer.train``, the program's epoch loop, with the benchmark's step log
in place of the history list that loop keeps.  The first three steps are
the ones the reference follows.  After them the same object trains through
the window: one more call into the epoch loop, which the step log stops at
the first step that completes after ``--seconds``; the window ends at the
completion before it.  The traffic file fixes the pool's sizes and the
sampler's epoch order of the bins, so every seed trains the same sequence
of bin sizes and the rate does not depend on which bins a seed's order
would put in the window.  Nothing else is set on the trainer that the
program's own entry does not set.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib
import math
import sys
import time
from typing import Dict, List, Sequence, Tuple

import numpy as np

from benchlib import checks
from benchlib.generator import Pool
from reference import mace as ref

CHECK_STEPS = 3
EPOCHS = 10**9  # the window, not the epoch count, ends a run
# Adam's first-moment decay in the program's optimizer: the first gradient
# the optimizer was given is m / (1 - b1) after one step.
ADAM_B1 = 0.9


class WindowClosed(Exception):
    """Raised from the history list at the first step that completes after
    the window's time is up; that step is not the window's."""


class StepLog(list):
    """The ``history`` list the epoch loop appends to: stamps each step's
    completion on the host clock and notes which bins it trained; with a
    ``deadline``, the first step completing after it closes the window."""

    def __init__(self, trainer, deadline=None):
        super().__init__()
        self.trainer = trainer
        self.deadline = deadline
        self.stamps: List[float] = []
        self.bins: List[List[List[int]]] = []
        self.positions: List[Tuple[int, int]] = []  # (step of epoch, of n)

    def append(self, metrics):
        now = time.perf_counter()
        if self.deadline is not None and now > self.deadline and self.stamps:
            raise WindowClosed
        super().append(metrics)
        self.stamps.append(now)
        st = self.trainer.sampler_state
        r = self.trainer.engine.n_ranks
        all_bins = self.trainer.sampler.bins_for_epoch(st.epoch)
        self.bins.append(all_bins[(st.cursor - 1) * r: st.cursor * r])
        self.positions.append((st.cursor - 1, len(all_bins) // r))


def train(tr, log: StepLog, max_steps=None) -> None:
    """``Trainer.train`` (the epoch loop) with ``log`` as its history."""
    run_epoch = type(tr).run_epoch
    tr.run_epoch = lambda history, **kw: run_epoch(tr, log, **kw)
    try:
        tr.train(n_epochs=EPOCHS, max_steps=max_steps)
    finally:
        del tr.run_epoch


@dataclasses.dataclass
class Built:
    trainer: object
    tcfg: object
    sizes: ref.Sizes


def program_config(config: dict):
    """(MaceConfig, TrainerConfig, notes) of a configuration file: the
    program's own values where the file names the program's entry."""
    from repro.core.mace import MaceConfig
    from repro.train.train_loop import TrainerConfig

    notes = []
    if config.get("program"):
        mod, fn = config["program"].split(":")
        mace_cfg, tcfg, _ = getattr(importlib.import_module(mod), fn)(
            ckpt_dir=None, **config.get("program_args", {}))
        for group, obj in (("model", mace_cfg), ("trainer", tcfg)):
            for key, want in config[group].items():
                have = getattr(obj, key)
                have = list(have) if isinstance(have, tuple) else have
                if have != want:
                    notes.append(f"program sets {group}.{key}={have!r}, "
                                 f"the configuration file {want!r}")
    else:
        model = {k: tuple(v) if isinstance(v, list) else v
                 for k, v in config["model"].items()}
        mace_cfg = MaceConfig(**model)
        tcfg = TrainerConfig(ckpt_dir=None, **config["trainer"])
    if tcfg.weight_decay:
        raise ValueError("the reference's Adam has no weight decay")
    return mace_cfg, tcfg, notes


def build(mace_cfg, tcfg, pool: Pool, seed: int, order_seed=None) -> Built:
    """The program's trainer on the pool; with ``order_seed``, its sampler
    orders each epoch's bins by that seed instead of the run's."""
    from repro.train.train_loop import Trainer

    trainer = Trainer(mace_cfg, tcfg, pool, seed=seed)
    if order_seed is not None:
        sampler = trainer.sampler
        sampler.seed = int(order_seed)
        trainer.sampler = sampler.with_ranks(sampler.n_ranks)
    return Built(trainer, trainer.tcfg, ref.Sizes.of(trainer.mace_cfg))


def check_fit(pool: Pool, trainer) -> None:
    """Every bin of the packing fits the bin's atom, edge and graph slots:
    the program would otherwise drop graphs, and the traffic is chosen so
    that no step has to."""
    shape = trainer.bin_shape
    for b in trainer.sampler.bins_for_epoch(0):
        atoms = int(pool.sizes[b].sum())
        edges = int(pool.n_edges[b].sum())
        if atoms > shape.max_nodes or edges > shape.max_edges or \
                len(b) > shape.max_graphs:
            raise ValueError(
                f"a bin of {len(b)} graphs, {atoms} atoms and {edges} edges "
                f"does not fit {shape}")


def host_tree(tree):
    import jax

    return jax.tree.map(lambda x: np.asarray(x, np.float32), tree)


def adam_first_moment(opt_state):
    """The first-moment tree of the optimizer state (a dict with 'm')."""
    import jax

    found = []

    def walk(x):
        if isinstance(x, dict) and "m" in x and "v" in x:
            found.append(x["m"])
        elif isinstance(x, (tuple, list)):
            for y in x:
                walk(y)
        elif isinstance(x, dict):
            for y in x.values():
                walk(y)

    walk(opt_state)
    if len(found) != 1:
        raise ValueError("no unique Adam first moment in the optimizer state")
    return jax.tree.map(lambda m: np.asarray(m, np.float32) / (1 - ADAM_B1),
                        found[0])


def program_readings(log: StepLog, p0, g1, p3):
    return {"losses": [float(h["loss"]) for h in list(log)[:CHECK_STEPS]],
            "grads": ref.leaf_norms(g1),
            "change": ref.leaf_norms(_sub(p3, p0))}


def _sub(a, b):
    import jax

    return jax.tree.map(lambda x, y: np.asarray(x, np.float32)
                        - np.asarray(y, np.float32), a, b)


def reference_readings(b: Built, pool: Pool, bins, seed: int,
                       dtype: str = "float32"):
    """The reference's readings over the first steps' bins."""
    t = b.tcfg
    steps = [[ref.collate([pool.get(i) for i in rank_bin], t.capacity,
                          t.capacity * t.edge_factor, t.max_graphs)
              for rank_bin in step] for step in bins]
    losses, g1, p0, p3 = ref.train_steps(
        seed, b.sizes, steps, lr=t.lr, clip_norm=t.clip_norm,
        energy_weight=t.energy_weight, forces_weight=t.forces_weight,
        dtype=dtype)
    return {"losses": losses, "grads": ref.leaf_norms(g1),
            "change": ref.leaf_norms(_sub(p3, p0))}


def first_steps(b: Built):
    """Drive the trainer through the checked steps by the window's own
    call; returns (log, initial params, first gradient, params after)."""
    tr = b.trainer
    p0 = host_tree(tr.params)
    log = StepLog(tr)
    train(tr, log, max_steps=1)
    g1 = adam_first_moment(tr.opt_state)
    train(tr, log, max_steps=CHECK_STEPS)
    return log, p0, g1, host_tree(tr.params)


@contextlib.contextmanager
def watched():
    """Note on standard error any compile and any full garbage collection
    inside the block (the window should have no compile)."""
    import jax

    compiles, collections, t0 = [], [], [0.0]

    def on_compile(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    def on_gc(phase, info):
        if phase == "start":
            t0[0] = time.perf_counter()
        elif info["generation"] == 2:
            collections.append(time.perf_counter() - t0[0])

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    gc.callbacks.append(on_gc)
    try:
        yield
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
        gc.callbacks.remove(on_gc)
    if compiles:
        print(f"note: {len(compiles)} programs compiled inside the window "
              f"({sum(compiles):.2f} s)", file=sys.stderr)
    if collections:
        print(f"note: {len(collections)} full garbage collections in the "
              f"window took {sum(collections):.3f} s", file=sys.stderr)


def stalls(ms: Sequence[float]) -> Dict:
    """The window's stalls, from each step's wall ms: the median step, the
    steps longer than twice it (how many, and their summed time over the
    median) and the longest step (its index in the window and its ms)."""
    med = float(np.median(ms))
    over = [m - med for m in ms if m > 2 * med]
    longest = int(np.argmax(ms))
    return {"median_ms": med, "count": len(over), "excess_ms": float(sum(over)),
            "longest": longest, "longest_ms": float(ms[longest])}


def stall_note(ms: Sequence[float], positions: Sequence[Tuple[int, int]]) -> str:
    """One line for standard error: ``stalls(ms)`` and where in its epoch
    the longest step fell."""
    s = stalls(ms)
    at, of = positions[s["longest"]]
    return (f"note: stalls: median step {s['median_ms']:.2f} ms; {s['count']} "
            f"steps over twice it, {s['excess_ms']:.1f} ms over the median in "
            f"all; longest step {s['longest']} of {len(ms)} "
            f"({s['longest_ms']:.1f} ms, step {at} of {of} in its epoch)")


def run(r) -> Dict:
    """One run of a training cell; ``r`` is the harness's run context."""
    marks = [("start", r.t_process)]

    def mark(label, t=None):
        marks.append((label, time.perf_counter() if t is None else t))

    mark("imports")
    mace_cfg, tcfg, notes = program_config(r.config)
    for n in notes:
        print(f"note: {n}", file=sys.stderr)
    pool = Pool(r.traffic, r_cut=float(mace_cfg.r_max), seed=r.seed)
    mark("traffic")
    b = build(mace_cfg, tcfg, pool, r.seed, r.traffic.get("order_seed"))
    check_fit(pool, b.trainer)
    tr = b.trainer
    mark("trainer")
    log, p0, g1, p3 = first_steps(b)
    mark("checked_steps")
    with StepProgram(tr.engine) as program:
        train(tr, log, max_steps=CHECK_STEPS + int(r.traffic["warmup_steps"]))
    prog = program_readings(log, p0, g1, p3)
    check_bins = log.bins[:CHECK_STEPS]

    spans = r.annotate(tr) if r.trace else None
    t_start = time.perf_counter()
    mark("warmup_steps", t_start)
    window = StepLog(tr, deadline=t_start + r.seconds)
    with watched(), r.window_trace():
        try:
            train(tr, window)
        except WindowClosed:
            pass
    t_end = window.stamps[-1]
    mark("window", t_end)
    steps = _steps(pool, window)
    steps[0]["ms"] = (window.stamps[0] - t_start) * 1e3
    print(stall_note([s["ms"] for s in steps], window.positions), file=sys.stderr)
    memory_peak = program.peak_bytes(r.devices[0])
    mark("memory")
    setup_s = t_start - r.t_process
    atoms = sum(s["atoms"] for s in steps)
    out = {
        "e2e": {"atoms_per_s": atoms / (t_end - t_start),
                "step_ms_p90": float(np.percentile([s["ms"] for s in steps], 90)),
                "setup_s": setup_s},
        "steps": steps,
        "memory_peak_bytes": memory_peak,
        "sizes": b.sizes,
        "slots": (tr.bin_shape.max_nodes, tr.bin_shape.max_edges),
        "host_spans": spans,
        "attempted": len(steps) + CHECK_STEPS,
        "failed": sum(not math.isfinite(float(h["loss"]))
                      for h in list(log)[:CHECK_STEPS] + list(window)),
    }
    if len(steps) < 100:
        print(f"note: the window held {len(steps)} steps, fewer than the 100 "
              "a 90th percentile needs", file=sys.stderr)

    # the program's state is freed before the reference runs on the chip
    del tr, b.trainer, log, window
    gc.collect()
    reference = reference_readings(b, pool, check_bins, r.seed)
    out["checks"] = checks.compare(prog, reference, r.limits)
    mark("reference")
    print("timing: " + ", ".join(f"{label} {t - prev:.2f} s" for (_, prev), (label, t)
                                 in zip(marks, marks[1:]))
          + f", window steps {len(steps)}", file=sys.stderr)
    return out


class StepProgram:
    """The engine's largest program, its per-bin gradient (the forces-in-loss
    backward), seen while the block around it runs: the wrapper sits on the
    engine instance and keeps the shapes of the program's arguments, so that
    its compiled footprint can be read after the window."""

    NAMES = ("_grad_fn", "_step_fn")

    def __init__(self, engine):
        self.engine = engine
        self.name = next(n for n in self.NAMES if getattr(engine, n, None))
        self.fn = getattr(engine, self.name)
        self.args = None

    def __enter__(self):
        import jax

        def spy(*args):
            self.args = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
                np.shape(x), x.dtype, sharding=getattr(x, "sharding", None)),
                args)
            return self.fn(*args)

        setattr(self.engine, self.name, spy)
        return self

    def __exit__(self, *exc):
        setattr(self.engine, self.name, self.fn)

    def peak_bytes(self, device) -> int:
        """The chip's peak while the program runs: its compiled peak
        (arguments, temporaries and outputs, from the persistent cache)
        plus what stays resident between steps besides its arguments."""
        stats = self.fn.lower(*self.args).compile().memory_analysis()
        program = int(getattr(stats, "peak_memory_in_bytes", 0)) or int(
            stats.argument_size_in_bytes + stats.output_size_in_bytes
            + stats.temp_size_in_bytes - stats.alias_size_in_bytes)
        resident = int((device.memory_stats() or {}).get("bytes_in_use", 0))
        other = max(0, resident - int(stats.argument_size_in_bytes))
        print(f"note: memory: {self.name} compiled peak {program} B "
              f"(arguments {stats.argument_size_in_bytes}, temporaries "
              f"{stats.temp_size_in_bytes}, outputs {stats.output_size_in_bytes}"
              f"), resident between steps {resident} B", file=sys.stderr)
        return program + other


def _steps(pool: Pool, log: StepLog) -> List[Dict]:
    """Per window step: its wall time since the previous step's completion
    (the first step's is set by the caller), and its real atoms and edges."""
    out = []
    for i, bins in enumerate(log.bins):
        idx = [j for b in bins for j in b]
        out.append({"ms": (log.stamps[i] - log.stamps[i - 1]) * 1e3 if i else 0.0,
                    "atoms": int(pool.sizes[idx].sum()),
                    "edges": int(pool.n_edges[idx].sum()),
                    "graphs": len(idx),
                    "rank_edges": [int(pool.n_edges[b].sum()) for b in bins]})
    return out
