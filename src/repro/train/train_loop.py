"""Trainer: fault-tolerant epoch loop over a pluggable execution engine.

The loop composes every substrate in the repo: balanced sampler (Algorithm 1
per epoch), static-shape collation driven through the async
``data.prefetch.PrefetchPipeline`` (``TrainerConfig.prefetch`` sets the
lookahead depth; 0 runs the same path inline), an execution engine
(``train.engine``: ``sequential`` per-bin oracle or real ``shard_map`` SPMD
over a device mesh) running the jitted value_and_grad step with optional
remat / int8-compressed data-parallel all-reduce, EMA, periodic atomic
checkpoints, and resume (params, opt state, EMA, sampler cursor all
restored).  ``simulate_failure_at`` lets tests kill the loop mid-epoch and
prove restart equivalence.  Per-rank step-time/load telemetry plus per-step
host collate/wait times are exposed via ``Trainer.engine.telemetry`` for the
straggler model and the host/device overlap report.  Each step is a
``train.step`` span (``repro.tracing``); while a profiler runs its
arguments are the step's counters: the ranks' real atoms as collated (when
the engine's host stats count them) and the backend compiles while the
step ran.

Elastic mid-run rescale
-----------------------
MACE's data parallelism is graph-level (one Algorithm-1 bin per rank, never
a partitioned graph), so changing the device count mid-run is a pure
host-side re-pack plus an engine rebuild — no model state is sharded by
rank except the compressed all-reduce's error-feedback residuals.
``Trainer.rescale(n_ranks)`` is that operation at a step boundary:

1. snapshot ``(params, opt_state, ema, ef, SamplerState)`` through the
   atomic checkpoint (a crash mid-rescale restores the pre-rescale run);
2. remap the sampler via ``sampler.rescale`` — the consumed bin prefix at
   the old rank count is excluded and the epoch *remainder* re-packed at
   the new one, so no graph is dropped or duplicated (the cursor-remap
   semantics documented in ``data.sampler``);
3. ``engine.close()`` then ``make_engine`` at the new rank count: fresh
   mesh, same params/opt/EMA, error-feedback residuals re-initialised to
   zeros at the new ``[R, ...]`` leading dim (``engine.init_ef`` contract);
4. the epoch loop re-enters a fresh prefetch pipeline (in-flight batches
   collated at the old rank count were drained and discarded).

``ElasticTrainer`` drives this from a ``{global_step: new_R}`` schedule
(the ``--rescale-at STEP:R`` fault drill), and checkpoints are *portable
across rank counts*: meta records ``n_ranks`` plus the epoch's rescale
lineage, so ``maybe_restore`` with ``TrainerConfig.elastic`` replays the
(deterministic) remap chain and continues a checkpoint written at R=4 on an
R=1 or R=2 trainer with params/opt/EMA restored exactly and EF re-init at
the new rank count.  tests/test_rescale.py proves rescale-equivalence
against an uninterrupted oracle and fault-injected restart at a different R.
"""
from __future__ import annotations

import dataclasses
import itertools
import os
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro import tracing
from repro.core.mace import MaceConfig, init_mace
from repro.resilience.faults import FaultPlan
from repro.resilience.heartbeat import (
    ENV_HEARTBEAT_DIR,
    HeartbeatWriter,
    StepWatchdog,
)
from repro.data.collate import BinShape
from repro.kernels import autotune
from repro.data.molecules import SyntheticCFMDataset
from repro.data.prefetch import PrefetchPipeline
from repro.data.sampler import (
    BalancedBatchSampler,
    FixedCountSampler,
    HierarchicalBalancedSampler,
    SamplerState,
)
from .checkpoint import latest_step, read_meta, restore_checkpoint, save_checkpoint
from .engine import RankTelemetry, make_engine
from .optimizer import EMA, adamw, chain, clip_by_global_norm


@dataclasses.dataclass
class TrainerConfig:
    capacity: int = 512
    edge_factor: int = 48
    max_graphs: int = 64
    n_ranks: int = 1                 # logical DP ranks (bins per step)
    lr: float = 5e-3
    weight_decay: float = 0.0
    clip_norm: float = 10.0
    ema_decay: float = 0.99
    energy_weight: float = 1.0
    forces_weight: float = 100.0
    remat: bool = False
    compress_grads: bool = False
    engine: str = "sequential"       # "sequential" | "shard_map" | "multihost"
    # pod topology: node count of the 2D ("node", "device") mesh.  Set ->
    # two-level Algorithm-1 packing (graphs -> ranks within a node, bins ->
    # nodes) and the hierarchical reduction (intra-node pmean, int8-EF
    # across nodes only).  None keeps the flat 1D layout.  n_ranks must be
    # divisible by n_nodes (ranks_per_node = n_ranks // n_nodes).
    n_nodes: Optional[int] = None
    prefetch: int = 0                # async collate lookahead depth (0 = inline)
    # overrides MaceConfig.impl (symcon + channelwise_tp contraction) when
    # set; "auto" resolves from the committed tuning table at build time
    impl: Optional[str] = None
    # overrides MaceConfig.interaction_impl when set ("ref" | "fused" |
    # "pallas" | "auto" | registered); None leaves the model config
    # untouched.  "auto" resolves impl + tile geometry + bwd_impl from the
    # tuning table (kernels.autotune) — block_n/block_e below are then
    # adopted from the decision so collation and kernel stay in lockstep.
    interaction_impl: Optional[str] = None
    # overrides MaceConfig.interaction_bwd_impl when set ("pallas" = the
    # dedicated backward kernel, "xla" = fused-XLA VJP fallback)
    interaction_bwd_impl: Optional[str] = None
    # overrides MaceConfig.precision when set ("fp32" | "bf16" | "fp8"):
    # reduced precisions run the pallas_<precision> kernel variants (operand
    # tile loads rounded, fp32 accumulation) and key the autotune lookup so
    # reduced-precision table rows never answer fp32 builds
    precision: Optional[str] = None
    # fused-interaction edge blocking tile shape (data.blocking); block_n
    # must match MaceConfig.interaction_block_n when blocking is consumed
    block_n: int = 32
    block_e: int = 128
    fixed_graphs_per_batch: int = 8   # baseline sampler's PyG-style count
    # elastic wiring: allow restoring a checkpoint written at a different
    # rank count (EF re-init + sampler lineage replay); ElasticTrainer and
    # the --rescale-at fault drill force this on
    elastic: bool = False
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 50
    log_every: int = 10
    # resilience wiring: directory for per-step heartbeat files (falls back
    # to the REPRO_HEARTBEAT_DIR env var a PodSupervisor sets for its
    # children), and an optional per-step wall-clock deadline — a step
    # exceeding it trips the StepWatchdog, converting a silent collective
    # stall into a loud, supervisor-visible failure (exit 44 by default)
    heartbeat_dir: Optional[str] = None
    step_deadline_s: Optional[float] = None


class Trainer:
    def __init__(
        self,
        mace_cfg: MaceConfig,
        tcfg: TrainerConfig,
        dataset: SyntheticCFMDataset,
        *,
        sampler: str = "balanced",
        seed: int = 0,
        mesh=None,
    ):
        if tcfg.impl is not None:
            mace_cfg = dataclasses.replace(mace_cfg, impl=tcfg.impl)
        if tcfg.interaction_impl is not None:
            mace_cfg = dataclasses.replace(
                mace_cfg, interaction_impl=tcfg.interaction_impl
            )
        if tcfg.interaction_bwd_impl is not None:
            mace_cfg = dataclasses.replace(
                mace_cfg, interaction_bwd_impl=tcfg.interaction_bwd_impl
            )
        if tcfg.precision is not None:
            mace_cfg = dataclasses.replace(mace_cfg, precision=tcfg.precision)
        # "auto" sentinels resolve against the committed tuning table (or
        # the roofline fallback) for THIS run's shape bucket — before the
        # BinShape is built, so an interaction decision's tile geometry can
        # flow into the collation contract (blk_* arrays + block_n check
        # below stay consistent by construction)
        self.autotune_decisions: Dict[str, "autotune.Decision"] = {}
        if autotune.needs_resolution(mace_cfg):
            mace_cfg, self.autotune_decisions = autotune.resolve_mace_config(
                mace_cfg, capacity=tcfg.capacity, edge_factor=tcfg.edge_factor
            )
            d = self.autotune_decisions.get("interaction")
            if d is not None and d.block_n is not None:
                tcfg = dataclasses.replace(
                    tcfg, block_n=int(d.block_n), block_e=int(d.block_e)
                )
        self.mace_cfg = mace_cfg
        self.tcfg = tcfg
        self.dataset = dataset
        self.bin_shape = BinShape.for_capacity(
            tcfg.capacity, tcfg.edge_factor, tcfg.max_graphs,
            block_n=tcfg.block_n, block_e=tcfg.block_e,
        )
        if sampler == "balanced":
            if tcfg.n_nodes:
                if tcfg.n_ranks % tcfg.n_nodes:
                    raise ValueError(
                        f"n_ranks={tcfg.n_ranks} not divisible by "
                        f"n_nodes={tcfg.n_nodes}"
                    )
                self.sampler = HierarchicalBalancedSampler(
                    dataset.sizes, tcfg.capacity, tcfg.n_nodes,
                    tcfg.n_ranks // tcfg.n_nodes, seed=seed,
                )
            else:
                self.sampler = BalancedBatchSampler(
                    dataset.sizes, tcfg.capacity, tcfg.n_ranks, seed=seed
                )
        else:
            self.sampler = FixedCountSampler(
                dataset.sizes, graphs_per_batch=tcfg.fixed_graphs_per_batch,
                n_ranks=tcfg.n_ranks, seed=seed,
            )

        self.optimizer = chain(
            clip_by_global_norm(tcfg.clip_norm),
            adamw(tcfg.lr, weight_decay=tcfg.weight_decay),
        )
        self.ema = EMA(tcfg.ema_decay)

        key = jax.random.PRNGKey(seed)
        self.params = init_mace(key, mace_cfg)
        self.opt_state = self.optimizer.init(self.params)
        self.ema_params = self.ema.init(self.params)
        self.global_step = 0
        self.sampler_state = SamplerState(epoch=0, cursor=0)
        self.engine = make_engine(
            tcfg.engine, mace_cfg, tcfg, self.optimizer, tcfg.max_graphs,
            mesh=mesh,
        )
        # blocking is one static tile geometry shared by data pipeline and
        # kernel; catch a mismatch before the first (mis-shaped) batch
        if getattr(self.engine, "with_blocking", False) and (
            self.bin_shape.block_n != mace_cfg.interaction_block_n
        ):
            raise ValueError(
                f"BinShape.block_n={self.bin_shape.block_n} != "
                f"MaceConfig.interaction_block_n={mace_cfg.interaction_block_n}"
            )
        # commit replicated state to the engine's mesh before the first
        # step — in multi-process runs the jitted step only accepts global
        # arrays, and even single-process mesh engines re-place on rescale
        self.params, self.opt_state, self.ema_params = self._place(
            (self.params, self.opt_state, self.ema_params)
        )
        # per-rank error-feedback residuals for the compressed all-reduce
        # (empty when compress_grads is off); checkpointed with the run.
        self.ef_state = self.engine.init_ef(self.params)
        # elastic rescale state: {global_step: new_R} fired at step
        # boundaries, this epoch's rescale lineage (how the current packing
        # derives from the full one — checkpointed for cross-R restore),
        # and the per-event timing records the benchmarks report.
        self.rescale_schedule: Dict[int, int] = {}
        self._lineage: List[Dict[str, int]] = []
        self.rescale_events: List[Dict[str, Any]] = []
        # telemetry of engines closed by past rescales (oldest first); the
        # whole-run view is ``self.telemetry``
        self.telemetry_generations: List[Any] = []
        # resilience: the env-armed chaos plan (empty when REPRO_FAULT_PLAN
        # is unset), the per-step liveness signal a PodSupervisor polls,
        # and the in-process step watchdog
        self.fault_plan = FaultPlan.from_env()
        hb_dir = tcfg.heartbeat_dir or os.environ.get(ENV_HEARTBEAT_DIR)
        self.heartbeat = (
            HeartbeatWriter(
                hb_dir, self._process_index, plan=self.fault_plan
            )
            if hb_dir
            else None
        )
        self.watchdog = (
            StepWatchdog(tcfg.step_deadline_s) if tcfg.step_deadline_s else None
        )

    @property
    def _process_index(self) -> int:
        return int(getattr(self.engine, "process_index", 0))

    @property
    def telemetry(self):
        """Whole-run telemetry: the live engine's ``RankTelemetry`` when no
        rescale has happened, else a ``RankTelemetry.merged`` view over
        every engine generation (closed ones + the live one) so calibration
        spans rescale events."""
        if not self.telemetry_generations:
            return self.engine.telemetry
        return RankTelemetry.merged(
            *self.telemetry_generations, self.engine.telemetry
        )

    # -------------------------- fault tolerance ---------------------------

    def _place(self, tree):
        """Engine hook: commit replicated state to the engine's mesh
        (identity for the sequential oracle)."""
        place = getattr(self.engine, "place_replicated", None)
        return place(tree) if place is not None else tree

    def _state(self):
        return {
            "params": self.params,
            "opt_state": self.opt_state,
            "ema": self.ema_params,
            "ef": self.ef_state,
        }

    def _host_state(self):
        """This process's checkpoint shard: engines with multi-process
        state (MultiHostEngine) reduce global arrays to their local host
        view; single-process engines checkpoint the state as-is."""
        state = self._state()
        to_host = getattr(self.engine, "host_state", None)
        return to_host(state) if to_host is not None else state

    def save(self):
        if not self.tcfg.ckpt_dir:
            return
        save_checkpoint(
            self.tcfg.ckpt_dir,
            self.global_step,
            self._host_state(),
            meta={
                "sampler": self.sampler_state.to_dict(),
                "n_ranks": self.engine.n_ranks,
                "lineage": [dict(h) for h in self._lineage],
            },
            process_index=getattr(self.engine, "process_index", 0),
            process_count=getattr(self.engine, "process_count", 1),
            barrier=getattr(self.engine, "barrier", None),
        )

    def maybe_restore(self) -> bool:
        d = self.tcfg.ckpt_dir
        if not d or latest_step(d) is None:
            return False
        step, meta = read_meta(d)
        eng_procs = int(getattr(self.engine, "process_count", 1))
        ckpt_ranks = int(meta.get("n_ranks", self.engine.n_ranks))
        ckpt_procs = int(meta.get("process_count", 1))
        cross_rank = ckpt_ranks != self.engine.n_ranks
        cross_proc = ckpt_procs != eng_procs
        if cross_rank and not self.tcfg.elastic:
            raise ValueError(
                f"checkpoint in {d} was written at n_ranks={ckpt_ranks} but "
                f"this trainer runs n_ranks={self.engine.n_ranks}; set "
                "TrainerConfig.elastic=True to restore across rank counts"
            )
        if cross_proc and not self.tcfg.elastic:
            raise ValueError(
                f"checkpoint in {d} was written by {ckpt_procs} process(es) "
                f"but this trainer runs {eng_procs}; set "
                "TrainerConfig.elastic=True to restore across host counts "
                "(losing a host is a rescale event)"
            )
        template = self._host_state()
        if cross_rank or cross_proc:
            # rank-local state (the error-feedback residuals, whose leading
            # dim and process layout are topology-bound) cannot be restored
            # across a topology change: leave it out of the template and
            # re-init below (documented contract, tests/test_rescale.py +
            # tests/test_multihost.py)
            template = {k: v for k, v in template.items() if k != "ef"}
        read_proc = int(getattr(self.engine, "process_index", 0))
        if cross_proc or read_proc >= ckpt_procs:
            # replicated state is identical in every writer's shard; shard 0
            # always exists regardless of either topology
            read_proc = 0
        step, state, meta = restore_checkpoint(
            d, template, step=step, process_index=read_proc,
            expect_process_count=None if self.tcfg.elastic else eng_procs,
        )
        # restore may have fallen back to an older committed step (payload
        # checksum mismatch) — everything below must track the step/meta it
        # actually RETURNED, not the newest step read_meta suggested
        ckpt_ranks = int(meta.get("n_ranks", ckpt_ranks))
        self.params = self._place(state["params"])
        self.opt_state = self._place(state["opt_state"])
        self.ema_params = self._place(state["ema"])
        if cross_rank or cross_proc:
            self.ef_state = self.engine.init_ef(self.params)
        else:
            from_host = getattr(self.engine, "ef_from_host", None)
            self.ef_state = (
                from_host(state["ef"]) if from_host is not None else state["ef"]
            )
        self.global_step = step
        st = SamplerState.from_dict(meta["sampler"])
        lineage = [dict(h) for h in meta.get("lineage", [])]
        if lineage or cross_rank:
            self.sampler, self.sampler_state, self._lineage = (
                self._replay_lineage(st, lineage, ckpt_ranks)
            )
        else:
            self.sampler_state = st
            self._lineage = []
        return True

    def _replay_lineage(self, st: SamplerState, lineage, ckpt_ranks: int):
        """Rebuild the checkpoint's epoch packing at *this* trainer's rank
        count: start from the full packing at the first hop's rank count,
        replay each recorded mid-epoch rescale (all deterministic — same
        sizes, capacity, seed), and append one more remap when the
        checkpoint's rank count differs from ours."""
        hops = lineage + [{"n_ranks": ckpt_ranks, "cursor": st.cursor}]
        sampler = self.sampler.with_ranks(int(hops[0]["n_ranks"]))
        for prev, nxt in zip(hops, hops[1:]):
            sampler, _ = sampler.rescale(
                int(nxt["n_ranks"]), SamplerState(st.epoch, int(prev["cursor"]))
            )
        state = SamplerState(st.epoch, int(hops[-1]["cursor"]))
        if self.engine.n_ranks != ckpt_ranks:
            sampler, state = sampler.rescale(self.engine.n_ranks, state)
            return sampler, state, hops
        return sampler, state, lineage

    # --------------------------- elastic rescale ---------------------------

    def rescale(self, n_ranks: int, *, mesh=None) -> Dict[str, Any]:
        """Elastic mid-run rescale at a step boundary (see module
        docstring): snapshot -> sampler cursor remap -> engine teardown +
        rebuild at ``n_ranks`` -> EF re-init.  Must not be called while an
        epoch's prefetch pipeline is live — schedule it via
        ``rescale_schedule`` / ``ElasticTrainer`` instead, which drains the
        pipeline first.  Returns the event record (timings land in the new
        engine's telemetry as ``repack_s`` / ``rebuild_s``)."""
        self.save()  # crash during the rebuild restores the pre-rescale run
        old_ranks = self.engine.n_ranks
        cursor = self.sampler_state.cursor
        t0 = time.perf_counter()
        self.sampler, self.sampler_state = self.sampler.rescale(
            n_ranks, self.sampler_state
        )
        repack_s = time.perf_counter() - t0
        self._lineage.append({"n_ranks": old_ranks, "cursor": cursor})
        t1 = time.perf_counter()
        self.telemetry_generations.append(self.engine.telemetry)
        self.engine.close()
        new_nodes = self.tcfg.n_nodes
        if new_nodes:
            # topology follows the sampler's with_ranks heuristic: keep
            # ranks_per_node when the new R divides into whole nodes (losing
            # a host = fewer nodes, same node width), else degrade to flat
            rpn = old_ranks // new_nodes
            new_nodes = n_ranks // rpn if rpn and n_ranks % rpn == 0 else None
        self.tcfg = dataclasses.replace(
            self.tcfg, n_ranks=n_ranks, n_nodes=new_nodes
        )
        self.engine = make_engine(
            self.tcfg.engine, self.mace_cfg, self.tcfg, self.optimizer,
            self.tcfg.max_graphs, mesh=mesh,
        )
        # replicated state is committed to the *old* mesh's devices;
        # re-place it on the new mesh before the first jitted step
        # (checkpoints stay device-free — logical addressing — so the
        # restore path re-places through the same hook)
        self.params, self.opt_state, self.ema_params = self._place(
            (self.params, self.opt_state, self.ema_params)
        )
        self.ef_state = self.engine.init_ef(self.params)
        rebuild_s = time.perf_counter() - t1
        self.engine.telemetry.record_rescale(repack_s, rebuild_s)
        event = {
            "step": self.global_step, "from_ranks": old_ranks,
            "to_ranks": n_ranks, "repack_s": repack_s,
            "rebuild_s": rebuild_s, "discarded_batches": 0,
        }
        self.rescale_events.append(event)
        return event

    # ------------------------------ loop ----------------------------------

    def _fetch_batch(self, rank_bins):
        """Host side of one step: materialise molecules and collate to the
        engine's device layout (plus host-stats dict: blocking seconds).
        Runs on the prefetch producer thread.

        Only ranks the engine declares process-local (``local_rank_range``)
        are materialised — in a multi-process run every process used to
        build all ranks' molecule lists and let collate slice its node's
        rows; non-local ranks now get an empty placeholder the engine's
        collate never touches, so host collate work is O(local ranks).

        Chaos sites: ``slow_collate`` (every call) and ``hang_at_step``
        (keyed to the live global step — exact with inline collate, ~1
        step of slack under prefetch lookahead) fire here, on the thread
        the pipeline runs collation on."""
        proc = self._process_index
        self.fault_plan.slow_collate(process=proc)
        self.fault_plan.hang_at_step(self.global_step, process=proc)
        local = getattr(self.engine, "local_rank_range", range(len(rank_bins)))
        with tracing.span("data.materialise"):
            mols_per_rank = [
                [self.dataset.get(i) for i in b] if r in local else []
                for r, b in enumerate(rank_bins)
            ]
        return self.engine.collate(mols_per_rank, self.bin_shape)

    @staticmethod
    def _step_counters(host_stats, compiles_before: int) -> Dict[str, Any]:
        """The ``train.step`` span's arguments: the backend compiles since
        the step began and, where collation counted them, the ranks' real
        atoms."""
        counters = {"compiles": tracing.compile_count() - compiles_before}
        if "atoms" in host_stats:
            counters["atoms"] = host_stats["atoms"]
        return counters

    def run_epoch(
        self,
        history,
        *,
        max_steps: Optional[int] = None,
        simulate_failure_at: Optional[int] = None,
    ) -> bool:
        """Run the rest of the current epoch (from the sampler cursor)
        through the prefetch pipeline: collation of step t+1 overlaps the
        device executing step t when ``tcfg.prefetch >= 1``.  A scheduled
        elastic rescale (``rescale_schedule``) fires at its step boundary:
        the pipeline is drained (in-flight old-rank-count batches
        discarded), ``rescale`` runs, and a fresh pipeline resumes the rest
        of the epoch at the new rank count.  Entries are popped once fired;
        an entry at the *current* step fires before any stepping, so a
        restart from the pre-rescale snapshot ``rescale`` writes at the
        boundary re-applies the rescale it was about to do.  Returns True
        when ``max_steps`` was reached (the run should stop)."""
        pipeline = None
        stop = False
        while True:
            if self.global_step in self.rescale_schedule:
                # either the loop just drained the pipeline for this entry,
                # or a restart resumed exactly at the boundary snapshot
                event = self.rescale(self.rescale_schedule.pop(self.global_step))
                if pipeline is not None:
                    event["discarded_batches"] = pipeline.discarded
            # the schedule outranks max_steps: a drill scheduled at the stop
            # step still fires above (the run then ends — and checkpoints —
            # at the new rank count) before this bound stops the loop
            items = self.sampler.step_iter(self.sampler_state)
            if max_steps is not None:
                # bound the producer's lookahead too: no collating (and then
                # discarding) batches past the stop point
                remaining = max_steps - self.global_step
                if remaining <= 0:
                    return True
                items = itertools.islice(items, remaining)
            with PrefetchPipeline(
                items,
                self._fetch_batch,
                depth=self.tcfg.prefetch,
            ) as pipeline:
                # the watchdog deadline spans the whole step: the wait on
                # the (possibly hung) collate producer AND the collective
                # engine step — armed before the pipeline wait, re-armed
                # after each completed step, disarmed on every exit path
                if self.watchdog is not None:
                    self.watchdog.arm(self.global_step)
                try:
                    while True:
                        with tracing.span(
                            "train.step", step_num=self.global_step
                        ) as step:
                            # counted only while a profiler records
                            compiles = (tracing.compile_count()
                                        if step.enabled else None)
                            item = next(pipeline, None)  # train.wait_batch
                            if item is None:
                                break
                            self._train_step(item, history, step, compiles,
                                             simulate_failure_at)
                        if self.global_step in self.rescale_schedule:
                            break  # leave the with-block: drain, fire at loop top
                        if max_steps and self.global_step >= max_steps:
                            stop = True
                            break
                finally:
                    if self.watchdog is not None:
                        self.watchdog.disarm()
            # the drain above (rescale boundary or max_steps) discards
            # in-flight batches but must never discard an in-flight producer
            # exception — a masked collate error would resurface steps later
            # (or never); surface it at the boundary instead
            pipeline.raise_pending()
            if stop:
                return True
            if self.global_step not in self.rescale_schedule:
                return False  # epoch stream exhausted, nothing pending

    def _train_step(self, item, history, step,
                    compiles_before: Optional[int],
                    simulate_failure_at) -> None:
        """One step on a prefetched batch, inside its ``train.step`` span."""
        batch, host_stats = item.batch
        # the step scalar must live on the engine's mesh too: a jitted
        # multi-process step rejects inputs committed to a single local
        # device (identity for the oracle)
        step_arr = self._place(jnp.asarray(self.global_step))
        self.params, self.opt_state, self.ef_state, metrics = self.engine.step(
            self.params, self.opt_state, self.ef_state, batch, step_arr
        )
        with tracing.span("train.ema_update"):
            self.ema_params = self.ema.update(
                self.ema_params, self.params, step_arr
            )
        self.global_step += 1
        self.sampler_state.cursor += 1
        self.engine.telemetry.record_host(
            item.collate_s, item.wait_s, host_stats.get("block_s", 0.0),
        )
        with tracing.span("train.metrics"):
            history.append({k: float(v) for k, v in metrics.items()})
        if compiles_before is not None:
            step.annotate(
                lambda: self._step_counters(host_stats, compiles_before))
        if self.heartbeat is not None:
            self.heartbeat.beat(self.global_step, self.sampler_state.epoch)
        if self.watchdog is not None:
            self.watchdog.check()
            self.watchdog.arm(self.global_step)

        if simulate_failure_at is not None and self.global_step >= simulate_failure_at:
            raise RuntimeError("simulated node failure")
        self.fault_plan.crash_at_step(
            self.global_step, process=self._process_index
        )
        if self.tcfg.ckpt_every and self.global_step % self.tcfg.ckpt_every == 0:
            with tracing.span("train.checkpoint"):
                self.save()

    def train(
        self,
        n_epochs: int = 1,
        *,
        max_steps: Optional[int] = None,
        simulate_failure_at: Optional[int] = None,
    ) -> Dict[str, Any]:
        history = []
        t_start = time.perf_counter()
        while self.sampler_state.epoch < n_epochs:
            if self.run_epoch(
                history,
                max_steps=max_steps,
                simulate_failure_at=simulate_failure_at,
            ):
                break
            self.sampler_state = SamplerState(self.sampler_state.epoch + 1, 0)
            self._lineage = []  # remainder universes are epoch-scoped
        self.save()
        return {"history": history, "wall": time.perf_counter() - t_start}


def parse_rescale_schedule(specs) -> Dict[int, int]:
    """Parse ``--rescale-at STEP:R`` fault-drill specs (a repeatable flag
    and/or comma-separated) into a ``{global_step: new_n_ranks}`` schedule."""
    schedule: Dict[int, int] = {}
    if isinstance(specs, str):
        specs = [specs]
    for spec in specs or []:
        for part in str(spec).split(","):
            part = part.strip()
            if not part:
                continue
            try:
                step_s, ranks_s = part.split(":")
                step, ranks = int(step_s), int(ranks_s)
            except ValueError:
                raise ValueError(
                    f"bad rescale spec {part!r}; want STEP:R"
                ) from None
            if step <= 0 or ranks <= 0:
                raise ValueError(
                    f"bad rescale spec {part!r}: STEP and R must be positive"
                )
            schedule[step] = ranks
    return schedule


class ElasticTrainer(Trainer):
    """Trainer wired for mid-run elasticity.

    ``rescale_schedule`` maps global step -> new rank count: when a step in
    the schedule completes, the epoch's prefetch pipeline drains (in-flight
    old-R batches discarded), the full state snapshots through the atomic
    checkpoint, the epoch remainder re-packs for the new rank count (exact
    cursor remap — ``data.sampler``), and a fresh mesh + engine are built
    before the loop resumes.  ``TrainerConfig.elastic`` is forced on so the
    checkpoints it writes restore across rank counts.
    """

    def __init__(
        self,
        mace_cfg: MaceConfig,
        tcfg: TrainerConfig,
        dataset: SyntheticCFMDataset,
        *,
        rescale_schedule: Optional[Dict[int, int]] = None,
        **kwargs,
    ):
        if not tcfg.elastic:
            tcfg = dataclasses.replace(tcfg, elastic=True)
        super().__init__(mace_cfg, tcfg, dataset, **kwargs)
        self.rescale_schedule = dict(rescale_schedule or {})
