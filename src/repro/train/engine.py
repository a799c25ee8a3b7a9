"""Execution engines: one step API, two data-parallel backends.

Architecture
------------
The paper's headline result is *distributed* training (Algorithm 1 bins ->
one bin per GPU per step -> gradient all-reduce).  Everything above the
optimizer update is therefore factored into an *engine* with one contract:

    engine.collate(mols_per_rank, bin_shape)
                       -> (backend batch layout, host stats: "block_s"
                           and, optionally, per-rank real "atoms")
    engine.init_ef(params)                   -> error-feedback residuals
    engine.step(params, opt_state, ef, batch, i)
                                    -> (params, opt_state, ef, metrics)
    engine.close()                           -> teardown (mesh + jit caches)

Engines are context managers and must be ``close()``-able mid-run: the
elastic rescale path (``train_loop.Trainer.rescale``) tears the engine down
at a step boundary and rebuilds one at a new rank count over a fresh mesh —
params/opt state carry over, error-feedback residuals are re-initialised at
the new R by ``init_ef`` (rank-local state cannot survive a change of R).

When the model's selected ``interaction`` impl consumes pre-blocked edges
(``kernels.registry`` capability ``consumes_blocking``; e.g. the fused
TP+scatter Pallas kernel), ``collate`` additionally emits the ``blk_*``
blocking arrays per rank (``data.blocking``) and reports the host seconds
spent blocking in the stats dict, which the trainer feeds to
``RankTelemetry.record_host`` so ``bench_scaling --measure-steps``
attributes the new host work.

and two interchangeable backends:

``SequentialEngine``
    The oracle.  Runs the jitted per-bin value-and-grad once per logical
    rank in a host loop, combines gradients exactly the way the distributed
    all-reduce would (mean, or — when ``compress_grads`` is set — the
    shared-scale int8 quantised sum with rank-local error feedback that
    mirrors ``compression.compressed_psum_ef``), then applies one optimizer
    update.  Because each rank's grad is computed in its own device
    dispatch, it also measures genuine *per-rank step times* — the
    telemetry that calibrates the straggler model.

``ShardMapEngine``
    The real SPMD backend.  ``data/collate.collate_stacked`` stacks the R
    collated bins on a leading ``[R, ...]`` axis; the whole train step
    (value_and_grad -> ``lax.pmean`` / ``compressed_psum_ef`` -> optimizer)
    runs under ``jax.shard_map`` on a ``("data",)`` mesh from
    ``launch.mesh.make_dp_mesh``, so one jitted program executes on all
    devices with the gradient all-reduce compiled in.  Params/opt state are
    replicated (``P()``); the batch and the error-feedback residuals are
    sharded on axis 0 (``P("data")``).

Both backends are numerically interchangeable (tests/test_engine.py proves
allclose over multi-step training on a forced multi-device CPU mesh), so the
sequential loop remains the reference semantics while shard_map provides the
scaling path every later feature (elastic rescale, multi-backend kernels via
``kernels.registry``) plugs into.

Mesh topology
-------------
Two mesh shapes back the SPMD engines:

* **1D ``("data",)``** (``ShardMapEngine``): every device is a peer; the
  gradient all-reduce — plain ``pmean`` or the int8 error-feedback
  ``compressed_psum_ef`` — spans the single axis.  Right for one host,
  where all device links are equal.
* **2D ``("node", "device")``** (``MultiHostEngine``): rows are hosts
  (one jax process per node in multi-process runs), columns are the
  devices inside a host.  The reduction is *hierarchical*: gradients are
  first ``lax.pmean``-ed over ``"device"`` — the intra-node hop rides
  NVLink/ICI-class links where bandwidth is cheap and quantisation would
  only cost accuracy — and only the **``"node"`` axis is compressed**
  (``compressed_psum_ef`` over ``"node"``), because the inter-node hop
  crosses the datacenter network where bandwidth is scarcest (the
  HydraGNN pod-scale lesson).  Error-feedback residuals are therefore
  keyed **per node** (``[n_nodes, ...]``, sharded ``P("node")``), not per
  rank: every device in a row holds the same post-``pmean`` gradient, so
  the node is the quantisation site.  The two-level Algorithm-1 packing
  (``core.binpack.two_level_batches``) mirrors the same topology on the
  data side — graphs -> ranks inside a node, bins -> nodes — so
  stragglers are balanced at both levels.  A single-node mesh
  (``n_nodes == 1``) short-circuits the compressed hop to the exact
  identity (``axis_size=1``): no wire, no quantisation drift.

Async host prefetch
-------------------
The ``collate``/``step`` split exists so the two can overlap: ``collate`` is
pure host (numpy) work and ``step`` releases the GIL while the device runs.
``data.prefetch.PrefetchPipeline`` exploits that — a bounded producer thread
runs ``engine.collate`` for step t+1 (up to ``depth`` steps ahead) while
``engine.step`` for step t executes, with deterministic ordering, clean
shutdown, and producer-exception propagation into the training loop.
``Trainer.run_epoch`` drives every epoch through the pipeline (``depth=0``
is the same code path run inline), and tests/test_engine.py's equivalence
harness proves prefetched training bit-streams the same batches and reaches
allclose params vs. the non-prefetched sequential oracle.

Kernel backward passes
----------------------
Since the engine step is value-and-grad, ~2/3 of its FLOPs are backward.
The registered Pallas impls carry hand-written backward kernels via
``jax.custom_vjp`` (registry capability ``has_custom_bwd``): the symmetric
contraction saves only its own ``(A_t, W_t)`` kernel inputs as residuals
and re-derives the sparse products on-chip, and the fused interaction saves
``(Y, h_node, R)`` plus the integer operands and blocking arrays — never a
per-edge ``[E, k, d_out]`` message tensor or any blocked copy (the backward
re-gathers blocked operands from the residuals exactly like the forward
does).  ``MaceConfig.interaction_bwd_impl`` / ``TrainerConfig.
interaction_bwd_impl`` select ``"pallas"`` (the dedicated backward kernel,
default; the second order runs on the same kernels) or ``"xla"`` (the
fused formulation's VJP — the fallback for capability-gated platforms).

Telemetry
---------
Each engine records a ``RankTelemetry``: per-step per-rank wall seconds
(sequential; shard_map reports the lock-step wall time), the durations of
the engine's own host spans (``repro.tracing``), and per-rank loads (real
atoms per bin: collation's host counter in the sequential oracle, the
step's own device count in the SPMD engines).  Telemetry is per engine *generation*: an elastic
rescale closes the engine and its telemetry with it, so the trainer keeps
the closed generations and ``RankTelemetry.merged(*generations)``
(``Trainer.telemetry``) provides the whole-run view — ``bench_scaling
--measure-steps`` calibration spans rescale events through it.  ``RankTelemetry.straggler_matrix()`` feeds
``core.binpack.balance_metrics(..., measured_work=...)`` so the straggler
ratio in the scaling benchmarks comes from *measured* numbers, not just the
token-count proxy; pass ``skip=1`` to drop the jit-compiling first step.
The trainer additionally folds the prefetch pipeline's per-step host
timings into the same object (``record_host``): ``overlap_seconds`` /
``overlap_fraction`` report how much of the Algorithm-1 collation cost was
hidden behind device compute.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core.mace import MaceConfig, weighted_loss
from repro.data.collate import BinShape, collate_bin, collate_stacked
from repro.kernels import registry
from repro.launch.mesh import make_dp_mesh, make_node_device_mesh
from repro.tracing import span
from .compression import compressed_psum_ef
from .optimizer import Transform, apply_updates

Params = Any
Batch = Dict[str, jnp.ndarray]
DP_AXIS = "data"
NODE_AXIS = "node"
DEVICE_AXIS = "device"


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RankTelemetry:
    """Per-step, per-rank measurements accumulated over a run.

    ``lockstep`` marks engines (shard_map) whose ranks execute one SPMD
    program: per-rank wall time is not separable there, so the recorded
    times are the lock-step step wall and ``straggler_matrix`` falls back
    to the per-rank *loads* (which are genuinely measured per rank).

    All summary methods take ``skip`` — pass ``skip=1`` when the run
    includes the first (jit-compiling) step, otherwise compilation time
    pollutes the calibration.
    """

    n_ranks: int
    lockstep: bool = False
    times: List[List[float]] = dataclasses.field(default_factory=list)
    loads: List[List[float]] = dataclasses.field(default_factory=list)
    # host-side prefetch telemetry (one scalar per step: collation is a
    # single producer thread, not per-rank work)
    host_collate: List[float] = dataclasses.field(default_factory=list)
    host_wait: List[float] = dataclasses.field(default_factory=list)
    # seconds of ``collate_s`` spent building the fused-interaction edge
    # blocking (a subset of host_collate; 0.0 when blocking is off)
    host_block: List[float] = dataclasses.field(default_factory=list)
    # elastic rescale events the trainer folded into this engine's run:
    # per event, host seconds re-packing bins (Algorithm 1 on the epoch
    # remainder) and seconds tearing down + rebuilding mesh/engine/EF state
    rescale_repack: List[float] = dataclasses.field(default_factory=list)
    rescale_rebuild: List[float] = dataclasses.field(default_factory=list)

    def record(self, times: Sequence[float], loads: Sequence[float]) -> None:
        assert len(times) == self.n_ranks and len(loads) == self.n_ranks
        self.times.append([float(t) for t in times])
        self.loads.append([float(l) for l in loads])

    def record_host(
        self, collate_s: float, wait_s: float, block_s: float = 0.0
    ) -> None:
        """Per-step host timings from the prefetch pipeline: seconds spent
        collating the batch, seconds the step loop blocked waiting for it
        (``wait == collate`` for the inline depth-0 path), and the part of
        the collate seconds spent on edge blocking."""
        self.host_collate.append(float(collate_s))
        self.host_wait.append(float(wait_s))
        self.host_block.append(float(block_s))

    def record_rescale(self, repack_s: float, rebuild_s: float) -> None:
        """One elastic rescale event: bin re-pack seconds + engine/mesh
        rebuild seconds (``bench_scaling --measure-steps --rescale-at``
        reports them as ``repack_s`` / ``engine_rebuild_s``)."""
        self.rescale_repack.append(float(repack_s))
        self.rescale_rebuild.append(float(rebuild_s))

    def rescale_seconds(self) -> tuple:
        """(total repack seconds, total engine-rebuild seconds)."""
        return float(np.sum(self.rescale_repack)), float(
            np.sum(self.rescale_rebuild)
        )

    @property
    def n_steps(self) -> int:
        return len(self.times)

    def work_matrix(self, skip: int = 0) -> np.ndarray:
        """[steps, ranks] wall seconds."""
        return np.asarray(
            self.times[skip:], dtype=np.float64
        ).reshape(-1, self.n_ranks)

    def load_matrix(self, skip: int = 0) -> np.ndarray:
        """[steps, ranks] real atoms per bin."""
        return np.asarray(
            self.loads[skip:], dtype=np.float64
        ).reshape(-1, self.n_ranks)

    def straggler_matrix(self, skip: int = 0) -> np.ndarray:
        """[steps, ranks] per-rank work for the straggler model — measured
        times where ranks are timed individually (sequential), measured
        loads where they run in lock-step (shard_map).  Feed to
        ``binpack.balance_metrics(measured_work=...)``."""
        return self.load_matrix(skip) if self.lockstep else self.work_matrix(skip)

    def c_token(self, skip: int = 0) -> float:
        """Calibrated per-token step cost (seconds/atom) for the epoch-time
        model in benchmarks/common.py.

        Lock-step engines take max-rank-load wall time per step (the whole
        step waits on the straggler), so dividing the step wall by the
        *max* rank load — not the mean — keeps the estimate unbiased."""
        t = self.work_matrix(skip)
        l = self.load_matrix(skip)
        if t.size == 0:
            return 0.0
        if self.lockstep:
            # one wall time per step (identical across the rank axis)
            return float(t[:, 0].sum()) / max(float(l.max(axis=1).sum()), 1.0)
        return float(t.sum()) / max(float(l.sum()), 1.0)

    def measured_straggler(self, skip: int = 0) -> float:
        """mean over steps of (max rank work / mean rank work)."""
        w = self.straggler_matrix(skip)
        if w.size == 0:
            return 1.0
        return float(np.mean(w.max(axis=1) / np.maximum(w.mean(axis=1), 1e-12)))

    # ------------------------- host/device overlap -------------------------

    def host_matrix(self, skip: int = 0) -> np.ndarray:
        """[steps, 2] host seconds per step: (collate_s, wait_s)."""
        return np.stack(
            [
                np.asarray(self.host_collate[skip:], np.float64),
                np.asarray(self.host_wait[skip:], np.float64),
            ],
            axis=1,
        ) if self.host_collate[skip:] else np.zeros((0, 2))

    def overlap_seconds(self, skip: int = 0) -> float:
        """Total collate seconds hidden behind device compute: per step
        ``max(collate_s - wait_s, 0)`` summed.  Zero for the inline path
        (the step loop waits for the whole collation every step)."""
        h = self.host_matrix(skip)
        if h.size == 0:
            return 0.0
        return float(np.maximum(h[:, 0] - h[:, 1], 0.0).sum())

    def overlap_fraction(self, skip: int = 0) -> float:
        """Fraction of total host collate time that was overlapped."""
        h = self.host_matrix(skip)
        if h.size == 0:
            return 0.0
        total = float(h[:, 0].sum())
        return self.overlap_seconds(skip) / total if total > 0 else 0.0

    def blocking_seconds(self, skip: int = 0) -> float:
        """Total host seconds spent building edge blockings (subset of the
        collate time; attributes the fused-interaction kernel's host-side
        preprocessing in scaling reports)."""
        return float(np.asarray(self.host_block[skip:], np.float64).sum())

    # --------------------- multi-generation merging ------------------------

    @classmethod
    def merged(cls, *generations: "RankTelemetry") -> "MergedTelemetry":
        """Multi-generation view over the telemetry of several engine
        *generations* (one per elastic-rescale segment, oldest first).

        Rank counts may differ across generations, so the per-generation
        time matrices stay separate (``work_matrices`` /
        ``straggler_matrices``) while every scalar summary — ``c_token``,
        ``measured_straggler``, host overlap/blocking totals, rescale
        seconds — aggregates over the whole run.  ``skip`` applies *per
        generation*: every rescale rebuilds mesh+engine and re-pays the jit
        compile on its first step, so each generation's warmup is dropped.
        This is what lets ``bench_scaling --measure-steps`` calibration span
        rescale events instead of reading only the newest engine's matrix.
        """
        if not generations:
            raise ValueError("merged() needs at least one generation")
        return MergedTelemetry(tuple(generations))


@dataclasses.dataclass(frozen=True)
class MergedTelemetry:
    """Read-only aggregate over ``RankTelemetry`` generations (see
    ``RankTelemetry.merged``).  Exposes the same summary surface minus the
    single-matrix accessors (rank counts differ across generations)."""

    generations: Tuple["RankTelemetry", ...]

    @property
    def n_generations(self) -> int:
        return len(self.generations)

    @property
    def n_steps(self) -> int:
        return sum(g.n_steps for g in self.generations)

    def work_matrices(self, skip: int = 0) -> List[np.ndarray]:
        """One [steps, ranks] wall-seconds matrix per generation."""
        return [g.work_matrix(skip) for g in self.generations]

    def straggler_matrices(self, skip: int = 0) -> List[np.ndarray]:
        """Per-generation straggler work (feed the *matching-rank-count*
        matrix to ``binpack.balance_metrics(measured_work=...)``)."""
        return [g.straggler_matrix(skip) for g in self.generations]

    def c_token(self, skip: int = 0) -> float:
        """Whole-run per-token cost: generation numerators/denominators are
        summed before dividing, so long generations weigh proportionally
        (each generation keeps its own lockstep semantics)."""
        num = den = 0.0
        for g in self.generations:
            t, l = g.work_matrix(skip), g.load_matrix(skip)
            if t.size == 0:
                continue
            if g.lockstep:
                num += float(t[:, 0].sum())
                den += float(l.max(axis=1).sum())
            else:
                num += float(t.sum())
                den += float(l.sum())
        return num / max(den, 1.0) if num else 0.0

    def measured_straggler(self, skip: int = 0) -> float:
        """Step-weighted mean over generations of max/mean rank work."""
        per_step = []
        for w in self.straggler_matrices(skip):
            if w.size:
                per_step.append(w.max(axis=1) / np.maximum(w.mean(axis=1), 1e-12))
        if not per_step:
            return 1.0
        return float(np.mean(np.concatenate(per_step)))

    def host_matrix(self, skip: int = 0) -> np.ndarray:
        """[steps, 2] (collate_s, wait_s) concatenated across generations —
        host telemetry is per-step scalar, so generations stack cleanly."""
        mats = [g.host_matrix(skip) for g in self.generations]
        mats = [m for m in mats if m.size]
        return np.concatenate(mats, axis=0) if mats else np.zeros((0, 2))

    def overlap_seconds(self, skip: int = 0) -> float:
        return float(sum(g.overlap_seconds(skip) for g in self.generations))

    def overlap_fraction(self, skip: int = 0) -> float:
        h = self.host_matrix(skip)
        total = float(h[:, 0].sum()) if h.size else 0.0
        return self.overlap_seconds(skip) / total if total > 0 else 0.0

    def blocking_seconds(self, skip: int = 0) -> float:
        return float(sum(g.blocking_seconds(skip) for g in self.generations))

    def rescale_seconds(self) -> tuple:
        """(total repack seconds, total engine-rebuild seconds)."""
        rs = [g.rescale_seconds() for g in self.generations]
        return (
            float(sum(r for r, _ in rs)),
            float(sum(b for _, b in rs)),
        )


# ---------------------------------------------------------------------------
# shared pieces
# ---------------------------------------------------------------------------


def make_loss_fn(mace_cfg: MaceConfig, tcfg, n_graphs: int) -> Callable:
    def loss_fn(params, batch):
        return weighted_loss(
            params, mace_cfg, batch, n_graphs,
            tcfg.energy_weight, tcfg.forces_weight,
        )

    if tcfg.remat:
        loss_fn = jax.checkpoint(loss_fn)
    return loss_fn


def _emulated_compressed_mean_ef(stacked_g, stacked_e):
    """Host-loop twin of ``compression.compressed_psum_ef`` on grads and
    error-feedback residuals stacked [R, ...]: per-rank residual added,
    shared pmax scale, int8-quantised per-rank payloads, integer sum,
    dequantise / R, new residuals kept rank-local.  Bit-matches the
    shard_map collective (the int16 wire sum is exact in f32 for R <= 258).
    Returns ``(g_hat_mean, new_stacked_e)``."""
    R = stacked_g.shape[0]
    c = stacked_g.astype(jnp.float32) + stacked_e
    scale = jnp.max(jnp.abs(c)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(c / scale), -127, 127)
    total = jnp.sum(q, axis=0)
    g_hat = (total * scale / R).astype(stacked_g.dtype)
    return g_hat, c - q * scale


def _emulated_hier_compressed_mean(stacked_g, stacked_e, *, n_nodes: int):
    """Host twin of the *hierarchical* reduction: grads stacked [R, ...]
    are first averaged inside each node (R = n_nodes * devices_per_node,
    node-major), then the per-node means go through the error-feedback
    int8 compression across nodes — residuals are [n_nodes, ...], one per
    quantisation site, exactly like ``MultiHostEngine``'s ``P("node")``
    EF shards.  ``n_nodes == 1`` mirrors the collective's ``axis_size=1``
    identity short-circuit (no quantisation, residual untouched).
    Returns ``(g_hat_mean, new_stacked_e)``."""
    R = stacked_g.shape[0]
    dpn = R // n_nodes
    node_g = jnp.mean(
        stacked_g.astype(jnp.float32).reshape((n_nodes, dpn) + stacked_g.shape[1:]),
        axis=1,
    )
    if n_nodes == 1:
        return node_g[0].astype(stacked_g.dtype), stacked_e
    c = node_g + stacked_e
    scale = jnp.max(jnp.abs(c)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(c / scale), -127, 127)
    total = jnp.sum(q, axis=0)
    g_hat = (total * scale / n_nodes).astype(stacked_g.dtype)
    return g_hat, c - q * scale


def _init_stacked_ef(params, n_ranks: int, compress: bool):
    """Per-rank error-feedback residuals, stacked [R, ...] (empty when the
    compressed all-reduce is off)."""
    if not compress:
        return ()
    return jax.tree.map(
        lambda p: jnp.zeros((n_ranks,) + p.shape, jnp.float32), params
    )


class RankBatches(list):
    """One step's per-rank device batches (the sequential oracle's batch
    layout), with each bin's real atoms as collation counted them on the
    host."""

    def __init__(self, batches: Sequence[Batch], loads: Sequence[int]):
        super().__init__(batches)
        self.loads = [float(n) for n in loads]


def _rank_load(batch: Batch) -> jnp.ndarray:
    return jnp.sum(batch["node_mask"].astype(jnp.float32))


def _optimizer_step(optimizer: Transform, grads, opt_state, params, step_idx):
    """(new params, new optimizer state), under the ``optimizer`` scope."""
    with jax.named_scope("optimizer"):
        updates, opt_state = optimizer.update(grads, opt_state, params, step_idx)
        return apply_updates(params, updates), opt_state


def interaction_consumes_blocking(mace_cfg: MaceConfig) -> bool:
    """True when the model's selected interaction impl exploits pre-blocked
    edges — the engine then asks collation to emit the ``blk_*`` arrays."""
    try:
        impl = registry.get_impl("interaction", mace_cfg.interaction_impl_name)
    except KeyError:
        return False
    return impl.consumes_blocking


# ---------------------------------------------------------------------------
# backends
# ---------------------------------------------------------------------------


class SequentialEngine:
    """Per-bin host loop over logical ranks — the oracle backend.

    Gradients are combined exactly as the distributed all-reduce would be,
    so a run with R logical ranks here equals a ShardMapEngine run with R
    devices (allclose; see tests/test_engine.py).
    """

    name = "sequential"

    def __init__(
        self, mace_cfg: MaceConfig, tcfg, optimizer: Transform, n_graphs: int
    ):
        self.n_ranks = tcfg.n_ranks
        self.compress = tcfg.compress_grads
        # n_nodes set -> emulate the 2D mesh's *hierarchical* reduction
        # (intra-node mean, int8-EF across nodes, per-node residuals) so
        # this engine stays the oracle for MultiHostEngine too
        self.n_nodes = getattr(tcfg, "n_nodes", None)
        if self.n_nodes and self.n_ranks % self.n_nodes:
            raise ValueError(
                f"n_ranks={self.n_ranks} not divisible by n_nodes={self.n_nodes}"
            )
        self.with_blocking = interaction_consumes_blocking(mace_cfg)
        self.telemetry = RankTelemetry(self.n_ranks)
        loss_fn = make_loss_fn(mace_cfg, tcfg, n_graphs)
        self._grad_fn = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))
        compress = self.compress
        n_nodes = self.n_nodes

        @jax.jit
        def finalize(params, opt_state, ef, stacked_grads, stacked_metrics, step_idx):
            # the host-loop twin of the SPMD engines' all-reduce
            with jax.named_scope("allreduce"):
                if compress:
                    reduce_ef = (
                        partial(_emulated_hier_compressed_mean, n_nodes=n_nodes)
                        if n_nodes
                        else _emulated_compressed_mean_ef
                    )
                    pairs = jax.tree.map(reduce_ef, stacked_grads, ef)
                    is_pair = lambda x: isinstance(x, tuple)
                    grads = jax.tree.map(lambda x: x[0], pairs, is_leaf=is_pair)
                    ef = jax.tree.map(lambda x: x[1], pairs, is_leaf=is_pair)
                else:
                    grads = jax.tree.map(partial(jnp.mean, axis=0), stacked_grads)
                metrics = jax.tree.map(partial(jnp.mean, axis=0), stacked_metrics)
            return (*_optimizer_step(optimizer, grads, opt_state, params,
                                     step_idx), ef, metrics)

        self._finalize = finalize

    def init_ef(self, params):
        """Fresh error-feedback residuals at *this engine's* rank count.

        Elastic-rescale contract: EF residuals are rank-local state with a
        ``[R, ...]`` leading dim — they cannot survive a change of R, so a
        rescale (or a cross-R checkpoint restore) re-initialises them to
        zeros here and the compressed path restarts its residual
        accumulation (tests/test_rescale.py asserts this contract).

        Hierarchical mode (``n_nodes`` set) keys residuals per *node* —
        the quantisation happens on per-node means, so the leading dim is
        ``n_nodes``, not ``n_ranks``."""
        lead = self.n_nodes if self.n_nodes else self.n_ranks
        return _init_stacked_ef(params, lead, self.compress)

    def place_replicated(self, tree):
        """Replicated-state placement hook (trivial here: the sequential
        oracle runs on the default device)."""
        return tree

    @property
    def local_rank_range(self) -> range:
        """Ranks whose molecules this process must materialise for
        ``collate`` (all of them: the oracle is single-process)."""
        return range(self.n_ranks)

    def close(self) -> None:
        """Teardown: drop the jitted step functions (clearing their
        compilation caches) so a successor engine at a different rank count
        can be built in the same process without leaked state.  Idempotent;
        ``step`` raises afterwards.  Engines are context managers."""
        for fn in (self._grad_fn, self._finalize):
            if fn is not None and hasattr(fn, "clear_cache"):
                fn.clear_cache()
        self._grad_fn = self._finalize = None

    @property
    def closed(self) -> bool:
        return self._grad_fn is None

    def __enter__(self):
        return self

    def __exit__(self, *exc_info):
        self.close()

    def collate(
        self, mols_per_rank: Sequence[Sequence[Any]], shape: BinShape
    ):
        stats = {"block_s": 0.0}
        with span("data.collate"):
            cols = [
                collate_bin(m, shape, with_blocking=self.with_blocking,
                            stats=stats)
                for m in mols_per_rank
            ]
        with span("data.to_device"):
            batches = [{k: jnp.asarray(v) for k, v in c.items()} for c in cols]
        return RankBatches(batches, stats["atoms"]), stats

    def step(self, params, opt_state, ef_state, batches: RankBatches, step_idx):
        if self.closed:
            raise RuntimeError("engine is closed (rescaled away?)")
        grads_l, metrics_l, times = [], [], []
        with span("engine.step"):
            for b in batches:
                with span("engine.grad") as dispatch:
                    (_, metrics), grads = self._grad_fn(params, b)
                with span("engine.grad_wait") as wait:
                    jax.block_until_ready(grads)
                times.append(dispatch.seconds + wait.seconds)
                grads_l.append(grads)
                metrics_l.append(metrics)
            with span("engine.stack"):
                stacked_g = jax.tree.map(lambda *g: jnp.stack(g), *grads_l)
                stacked_m = jax.tree.map(lambda *m: jnp.stack(m), *metrics_l)
            with span("engine.finalize"):
                params, opt_state, ef_state, metrics = self._finalize(
                    params, opt_state, ef_state, stacked_g, stacked_m, step_idx
                )
        self.telemetry.record(times, batches.loads)
        return params, opt_state, ef_state, metrics


class ShardMapEngine:
    """Real SPMD data parallelism: one device per rank under ``shard_map``.

    The jitted step shards the stacked ``[R, ...]`` batch over the mesh's
    ``data`` axis, runs value-and-grad per device, all-reduces gradients
    (``lax.pmean``, or ``compressed_psum`` when ``compress_grads``), and
    applies the optimizer update on replicated params — exactly one compiled
    program per BinShape, collective included.
    """

    name = "shard_map"

    def __init__(
        self,
        mace_cfg: MaceConfig,
        tcfg,
        optimizer: Transform,
        n_graphs: int,
        *,
        mesh=None,
    ):
        self.n_ranks = tcfg.n_ranks
        self.mesh = mesh if mesh is not None else make_dp_mesh(self.n_ranks)
        mesh_dp = int(np.prod(self.mesh.devices.shape))
        if mesh_dp != self.n_ranks:
            raise ValueError(
                f"mesh has {mesh_dp} devices but engine needs n_ranks={self.n_ranks}"
            )
        self.compress = tcfg.compress_grads
        self.with_blocking = interaction_consumes_blocking(mace_cfg)
        self.telemetry = RankTelemetry(self.n_ranks, lockstep=True)
        loss_fn = make_loss_fn(mace_cfg, tcfg, n_graphs)
        compress = self.compress

        def rank_step(params, opt_state, ef, batch, step_idx):
            batch = jax.tree.map(lambda x: x[0], batch)  # [1, ...] block -> [...]
            (_, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch
            )
            with jax.named_scope("allreduce"):
                if compress:
                    pairs = jax.tree.map(
                        lambda g, e: compressed_psum_ef(g, e[0], DP_AXIS),
                        grads, ef,
                    )
                    is_pair = lambda x: isinstance(x, tuple)
                    grads = jax.tree.map(lambda x: x[0], pairs, is_leaf=is_pair)
                    ef = jax.tree.map(lambda x: x[1][None], pairs, is_leaf=is_pair)
                else:
                    grads = jax.lax.pmean(grads, DP_AXIS)
                metrics = jax.lax.pmean(metrics, DP_AXIS)
            load = _rank_load(batch)[None]               # [1] -> gathers to [R]
            return (*_optimizer_step(optimizer, grads, opt_state, params,
                                     step_idx), ef, metrics, load)

        # check_vma=False: under the varying-manual-axes check, jax.grad
        # psums the cotangent of every replicated param itself (the
        # transpose of the implicit pvary), so the pmean below would
        # average a sum; and pallas_call out_shapes carry no vma
        self._step_fn = jax.jit(
            shard_map(
                rank_step,
                mesh=self.mesh,
                in_specs=(P(), P(), P(DP_AXIS), P(DP_AXIS), P()),
                out_specs=(P(), P(), P(DP_AXIS), P(), P(DP_AXIS)),
                check_vma=False,
            )
        )

    def init_ef(self, params):
        """Fresh ``[R, ...]`` error-feedback residuals for this engine's
        rank count (see SequentialEngine.init_ef for the rescale contract)."""
        return _init_stacked_ef(params, self.n_ranks, self.compress)

    def place_replicated(self, tree):
        """Commit replicated state (params/opt/EMA/step scalar) onto this
        engine's mesh.  The elastic-rescale path needs it explicitly: state
        committed to the *previous* mesh's devices must be re-placed before
        the first jitted step on the new mesh."""
        replicated = jax.sharding.NamedSharding(self.mesh, P())
        return jax.device_put(tree, replicated)

    @property
    def local_rank_range(self) -> range:
        """Ranks whose molecules this process must materialise for
        ``collate`` (all of them: one host drives the whole 1D mesh)."""
        return range(self.n_ranks)

    def close(self) -> None:
        """Teardown: clear the jitted SPMD step's compilation cache and drop
        the mesh reference.  The engine used to assume its mesh outlives it;
        explicit close makes serial engines over *different* device counts
        safe in one process (elastic rescale rebuilds through here —
        tests/test_rescale.py constructs R=4 then R=2 engines serially).
        Idempotent; ``step`` raises afterwards."""
        if self._step_fn is not None and hasattr(self._step_fn, "clear_cache"):
            self._step_fn.clear_cache()
        self._step_fn = None
        self.mesh = None

    @property
    def closed(self) -> bool:
        return self._step_fn is None

    def __enter__(self):
        return self

    def __exit__(self, *exc_info):
        self.close()

    def collate(
        self, mols_per_rank: Sequence[Sequence[Any]], shape: BinShape
    ):
        if len(mols_per_rank) != self.n_ranks:
            raise ValueError(
                f"got {len(mols_per_rank)} bins for {self.n_ranks} ranks"
            )
        stats = {"block_s": 0.0}
        with span("data.collate"):
            arrs = collate_stacked(
                mols_per_rank, shape, with_blocking=self.with_blocking,
                stats=stats,
            )
        # each rank's bin goes straight to its own device
        with span("data.to_device"):
            batch = jax.device_put(
                arrs, jax.sharding.NamedSharding(self.mesh, P(DP_AXIS))
            )
        return batch, stats

    def step(self, params, opt_state, ef_state, batch: Batch, step_idx):
        if self.closed:
            raise RuntimeError("engine is closed (rescaled away?)")
        with span("engine.step") as whole:
            params, opt_state, ef_state, metrics, loads = self._step_fn(
                params, opt_state, ef_state, batch, step_idx
            )
            with span("engine.wait"):
                jax.block_until_ready(params)
        # lock-step SPMD: per-rank wall time is indistinguishable on one
        # host, so each rank is charged the step wall; loads stay per-rank
        # (telemetry is marked lockstep so straggler_matrix uses loads).
        self.telemetry.record(
            [whole.seconds] * self.n_ranks, [float(x) for x in np.asarray(loads)]
        )
        return params, opt_state, ef_state, metrics


class MultiHostEngine:
    """Hierarchical SPMD data parallelism over a 2D ``("node", "device")``
    mesh — the pod-scale backend (see the module docstring's *Mesh
    topology* section).

    One jax process per node in multi-process runs (brought up via
    ``launch.multihost.initialize_distributed``); a single process can
    also *emulate* the topology over forced host devices for tests.  The
    jitted step runs value-and-grad per device, ``lax.pmean``s gradients
    over the intra-node ``"device"`` axis, then reduces the per-node
    means across ``"node"`` — plain ``pmean``, or ``compressed_psum_ef``
    (int8 + per-node error feedback) when ``compress_grads`` — and
    applies the optimizer update on replicated params.

    Multi-process state placement: batches are built from each process's
    *local* bins (``make_array_from_process_local_data``), EF residuals
    are ``P("node")``-sharded global arrays, and replicated state flows
    through ``place_replicated``/``host_state``/``ef_from_host`` so the
    trainer's checkpoint path stays process-local (every process writes
    ``arrays.<proc>.npz``; commit is barrier'd — see train.checkpoint).
    """

    name = "multihost"

    def __init__(
        self,
        mace_cfg: MaceConfig,
        tcfg,
        optimizer: Transform,
        n_graphs: int,
        *,
        mesh=None,
    ):
        self.n_ranks = tcfg.n_ranks
        n_nodes = getattr(tcfg, "n_nodes", None) or jax.process_count()
        if self.n_ranks % n_nodes:
            raise ValueError(
                f"n_ranks={self.n_ranks} not divisible by n_nodes={n_nodes}"
            )
        self.n_nodes = n_nodes
        self.devices_per_node = self.n_ranks // n_nodes
        self.process_index = jax.process_index()
        self.process_count = jax.process_count()
        if mesh is None:
            mesh = make_node_device_mesh(n_nodes, self.devices_per_node)
        if tuple(mesh.axis_names) != (NODE_AXIS, DEVICE_AXIS) or (
            mesh.devices.shape != (n_nodes, self.devices_per_node)
        ):
            raise ValueError(
                f"multihost engine needs a ({n_nodes}, {self.devices_per_node}) "
                f"(node, device) mesh, got {mesh.devices.shape} over "
                f"{mesh.axis_names}"
            )
        self.mesh = mesh
        self.compress = tcfg.compress_grads
        self.with_blocking = interaction_consumes_blocking(mace_cfg)
        self.telemetry = RankTelemetry(self.n_ranks, lockstep=True)
        loss_fn = make_loss_fn(mace_cfg, tcfg, n_graphs)
        compress = self.compress

        def rank_step(params, opt_state, ef, batch, step_idx):
            batch = jax.tree.map(lambda x: x[0], batch)  # [1, ...] block -> [...]
            (_, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch
            )
            with jax.named_scope("allreduce"):
                # level 1: intra-node mean over the fast ("device") links —
                # cheap bandwidth, so no quantisation here
                grads = jax.lax.pmean(grads, DEVICE_AXIS)
                if compress:
                    # level 2: int8-EF across nodes only, where bandwidth is
                    # scarce; residual is per-node (identical on every device
                    # of a row, since the inputs are post-pmean)
                    pairs = jax.tree.map(
                        lambda g, e: compressed_psum_ef(
                            g, e[0], NODE_AXIS, axis_size=n_nodes
                        ),
                        grads, ef,
                    )
                    is_pair = lambda x: isinstance(x, tuple)
                    grads = jax.tree.map(lambda x: x[0], pairs, is_leaf=is_pair)
                    ef = jax.tree.map(lambda x: x[1][None], pairs, is_leaf=is_pair)
                else:
                    grads = jax.lax.pmean(grads, NODE_AXIS)
                metrics = jax.lax.pmean(metrics, (NODE_AXIS, DEVICE_AXIS))
                # node-major [R] of per-rank loads, replicated so the host
                # can read it from any process (telemetry feeds
                # two_level_metrics)
                loads = jax.lax.all_gather(
                    _rank_load(batch)[None], (NODE_AXIS, DEVICE_AXIS),
                    tiled=True,
                )
            return (*_optimizer_step(optimizer, grads, opt_state, params,
                                     step_idx), ef, metrics, loads)

        # check_vma=False for the reasons given in ShardMapEngine
        self._step_fn = jax.jit(
            shard_map(
                rank_step,
                mesh=self.mesh,
                in_specs=(
                    P(), P(), P(NODE_AXIS), P((NODE_AXIS, DEVICE_AXIS)), P(),
                ),
                out_specs=(P(), P(), P(NODE_AXIS), P(), P()),
                check_vma=False,
            )
        )

    # ------------------------- state placement ---------------------------

    @staticmethod
    def _leaf_to_host(x):
        """np view of a leaf: addressable shard for global arrays (whose
        full value np.asarray cannot touch), plain asarray otherwise."""
        if isinstance(x, jax.Array) and not x.is_fully_addressable:
            return np.asarray(x.addressable_shards[0].data)
        return np.asarray(x)

    def place_replicated(self, tree):
        """Replicated state (params/opt/EMA/step scalar) -> global arrays
        on the 2D mesh.  Multi-process: every process contributes its
        (identical) host copy via ``make_array_from_process_local_data``;
        single-process emulation is a plain device_put."""
        sh = jax.sharding.NamedSharding(self.mesh, P())
        if self.process_count == 1:
            return jax.device_put(tree, sh)
        return jax.tree.map(
            lambda x: jax.make_array_from_process_local_data(
                sh, self._leaf_to_host(x)
            ),
            tree,
        )

    def host_state(self, tree):
        """Checkpointable host view: replicated leaves become their full
        np value, ``P("node")``-sharded EF leaves become this process's
        own ``[1, ...]`` node shard (single-process: the full
        ``[n_nodes, ...]`` stack — everything is addressable)."""
        return jax.tree.map(self._leaf_to_host, tree)

    def ef_from_host(self, ef_host):
        """Rebuild the ``P("node")``-sharded EF residuals from their
        ``host_state`` form."""
        if isinstance(ef_host, tuple) and ef_host == ():
            return ()
        sh = jax.sharding.NamedSharding(self.mesh, P(NODE_AXIS))
        if self.process_count == 1:
            return jax.tree.map(
                lambda e: jax.device_put(jnp.asarray(e, jnp.float32), sh),
                ef_host,
            )

        def one(e):
            local = np.asarray(e, np.float32)  # [1, ...]: our node's row
            gshape = (self.n_nodes,) + local.shape[1:]
            return jax.make_array_from_callback(gshape, sh, lambda idx: local)

        return jax.tree.map(one, ef_host)

    def barrier(self, name: str) -> None:
        """Cross-process sync point (checkpoint commit protocol).  No-op
        in single-process emulation."""
        if self.process_count > 1:
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices(name)

    # ----------------------------- engine API -----------------------------

    @property
    def local_rank_range(self) -> range:
        """Ranks whose molecules this process must materialise for
        ``collate``: only this node's contiguous node-major slice in
        multi-process runs (``collate`` builds the batch from exactly these
        bins via ``make_array_from_process_local_data``), every rank in
        single-process emulation.  The trainer's ``_fetch_batch`` consults
        this so non-local ranks are never loaded or collated (the PR-8
        every-process-collates-everything residual)."""
        if self.process_count > 1:
            lo = self.process_index * self.devices_per_node
            return range(lo, lo + self.devices_per_node)
        return range(self.n_ranks)

    def init_ef(self, params):
        """Fresh ``[n_nodes, ...]`` error-feedback residuals, sharded
        ``P("node")`` (one residual per quantisation site — see the module
        docstring; the rescale contract matches SequentialEngine)."""
        if not self.compress:
            return ()
        sh = jax.sharding.NamedSharding(self.mesh, P(NODE_AXIS))

        def one(p):
            gshape = (self.n_nodes,) + p.shape
            return jax.make_array_from_callback(
                gshape, sh, lambda idx, g=gshape: np.zeros(g, np.float32)[idx]
            )

        return jax.tree.map(one, params)

    def close(self) -> None:
        """Teardown (see ShardMapEngine.close): clear the SPMD step's jit
        cache and drop the mesh so a successor engine can rebuild."""
        if self._step_fn is not None and hasattr(self._step_fn, "clear_cache"):
            self._step_fn.clear_cache()
        self._step_fn = None
        self.mesh = None

    @property
    def closed(self) -> bool:
        return self._step_fn is None

    def __enter__(self):
        return self

    def __exit__(self, *exc_info):
        self.close()

    def collate(
        self, mols_per_rank: Sequence[Sequence[Any]], shape: BinShape
    ):
        if len(mols_per_rank) != self.n_ranks:
            raise ValueError(
                f"got {len(mols_per_rank)} bins for {self.n_ranks} ranks"
            )
        stats = {"block_s": 0.0}
        local = mols_per_rank
        if self.process_count > 1:
            # each process collates only its own node's bins (node-major
            # rank order: rank r -> node r // dpn) and contributes them as
            # the local shard of the global [R, ...] batch
            lo = self.process_index * self.devices_per_node
            local = mols_per_rank[lo:lo + self.devices_per_node]
        with span("data.collate"):
            arrs = collate_stacked(
                local, shape, with_blocking=self.with_blocking, stats=stats
            )
        with span("data.to_device"):
            if self.process_count > 1:
                sh = jax.sharding.NamedSharding(
                    self.mesh, P((NODE_AXIS, DEVICE_AXIS))
                )
                batch = {
                    k: jax.make_array_from_process_local_data(
                        sh, v, (self.n_ranks,) + v.shape[1:]
                    )
                    for k, v in arrs.items()
                }
            else:
                batch = {k: jnp.asarray(v) for k, v in arrs.items()}
        return batch, stats

    def step(self, params, opt_state, ef_state, batch: Batch, step_idx):
        if self.closed:
            raise RuntimeError("engine is closed (rescaled away?)")
        with span("engine.step") as whole:
            params, opt_state, ef_state, metrics, loads = self._step_fn(
                params, opt_state, ef_state, batch, step_idx
            )
            with span("engine.wait"):
                jax.block_until_ready(params)
        self.telemetry.record(
            [whole.seconds] * self.n_ranks, [float(x) for x in np.asarray(loads)]
        )
        return params, opt_state, ef_state, metrics


ENGINES = {
    SequentialEngine.name: SequentialEngine,
    ShardMapEngine.name: ShardMapEngine,
    MultiHostEngine.name: MultiHostEngine,
}


def make_engine(
    name: str,
    mace_cfg: MaceConfig,
    tcfg,
    optimizer: Transform,
    n_graphs: int,
    *,
    mesh=None,
):
    """Engine factory: ``name`` in {"sequential", "shard_map", "multihost"}.

    A ``mace_cfg`` still carrying an ``"auto"`` impl sentinel is resolved
    here against the committed tuning table (``kernels.autotune``) as a
    safety net for callers that build engines directly.  The tile-geometry
    search space is pinned to ``(tcfg.block_n, tcfg.block_e)`` — the
    collation contract is already fixed at this layer, so the decision may
    pick impl/bwd but must not diverge from the batch's blocking shapes
    (the Trainer resolves *before* building its BinShape and can adopt the
    decision's geometry instead).
    """
    try:
        cls = ENGINES[name]
    except KeyError:
        raise KeyError(
            f"unknown engine {name!r}; available: {sorted(ENGINES)}"
        ) from None
    from repro.kernels import autotune

    if autotune.needs_resolution(mace_cfg):
        mace_cfg, _ = autotune.resolve_mace_config(
            mace_cfg,
            capacity=tcfg.capacity,
            edge_factor=tcfg.edge_factor,
            block_candidates=[(tcfg.block_n, tcfg.block_e)],
        )
    if cls in (ShardMapEngine, MultiHostEngine):
        return cls(mace_cfg, tcfg, optimizer, n_graphs, mesh=mesh)
    return cls(mace_cfg, tcfg, optimizer, n_graphs)
