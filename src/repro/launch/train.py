"""Cluster training entry point.

``--arch mace_cfm`` trains the paper's MACE CFM configuration
(``configs/mace_cfm.py``) through ``Trainer`` with the kernels the
autotuner picks for the platform, on bins of ``FULL_CAPACITY`` atoms: the
largest capacity whose training step fits one 16 GB v5e chip.  One process
drives the chips it sees; ``--distributed`` joins a multi-process group
(``jax.distributed.initialize()`` with an explicit coordinator).
``--reduced`` runs a CPU-sized configuration.

    PYTHONPATH=src python -m repro.launch.train --arch mace_cfm --steps 100
    PYTHONPATH=src python -m repro.launch.train --arch qwen3-14b --reduced --steps 10

Fault tolerance: atomic checkpoints every --ckpt-every steps; on restart the
trainer auto-resumes (params, optimizer, EMA, sampler cursor).  Elastic
rescale: if the restarted world size differs, Algorithm 1 re-packs bins for
the new rank count (host-side, milliseconds).

Supervised pods (``--distributed --supervised``): this process becomes a
``PodSupervisor`` parent instead of a trainer — it spawns ``--nprocs``
copies of this same command (minus ``--supervised``) as one jax process
group, watches exit codes + per-step heartbeats, and on a crash or hang
kills the group and relaunches at degraded world size from the newest
committed checkpoint (elastic restore), within ``--max-restarts``:

    PYTHONPATH=src python -m repro.launch.train \
        --distributed --supervised --nprocs 2 --reduced --steps 20
"""
from __future__ import annotations

import argparse
import os

# Bin capacity (atoms) of the full-size MACE CFM run: the largest whose
# training step, compiled for a v5e chip, peaked within 95% of the 15.75 GB
# the chip offers while the interaction's second order ran as XLA (memory
# analysis: 11.5 GB at 1024, 14.05 GB at 1280, 14.87 GB at 1408, 15.51 GB
# at 1472; its [E, k, nnz] temporaries grew with the bin).  On the kernels,
# the per-bin gradient at 1408 peaks at about 4 GB, so the bin can grow.
FULL_CAPACITY = 1408
REDUCED_CAPACITY = 256


def mace_cfm_run(reduced: bool = False, **tcfg_overrides):
    """``(MaceConfig, TrainerConfig, dataset)`` of the MACE CFM training
    run: autotuned kernels, ``FULL_CAPACITY``-atom bins over the Table-3
    synthetic mixture (``reduced``: the CPU-sized config and bins)."""
    from repro.configs.mace_cfm import CONFIG, REDUCED
    from repro.data.molecules import SyntheticCFMDataset
    from repro.train.train_loop import TrainerConfig

    cap = REDUCED_CAPACITY if reduced else FULL_CAPACITY
    ds = SyntheticCFMDataset(
        2000 if reduced else 100_000, seed=0,
        max_atoms=cap // 4 if reduced else None,
    )
    kw = dict(impl="auto", interaction_impl="auto", capacity=cap,
              edge_factor=32, max_graphs=max(16, cap // 8))
    kw.update(tcfg_overrides)
    return (REDUCED if reduced else CONFIG), TrainerConfig(**kw), ds


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mace_cfm")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--reduced", action="store_true",
                    help="run the reduced config (CPU-sized)")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--distributed", action="store_true",
                    help="join a multi-process group (see --coordinator)")
    ap.add_argument("--coordinator", default=None,
                    help="HOST:PORT of process 0 (or env REPRO_COORDINATOR)")
    ap.add_argument("--num-processes", type=int, default=None,
                    help="world size (or env REPRO_NUM_PROCESSES)")
    ap.add_argument("--process-id", type=int, default=None,
                    help="this process's rank (or env REPRO_PROCESS_ID)")
    ap.add_argument("--engine", default=None,
                    help="engine override (sequential|shard_map|multihost)")
    ap.add_argument("--n-nodes", type=int, default=None,
                    help="node count for the 2D (node, device) mesh")
    ap.add_argument("--n-ranks", type=int, default=None,
                    help="total data-parallel ranks (devices)")
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--elastic", action="store_true",
                    help="allow restoring a checkpoint written at a "
                         "different rank/host count (implied by "
                         "--supervised relaunches)")
    ap.add_argument("--supervised", action="store_true",
                    help="run as a PodSupervisor parent: spawn --nprocs "
                         "children of this command, monitor heartbeats + "
                         "exit codes, restart elastically on failure")
    ap.add_argument("--nprocs", type=int, default=2,
                    help="supervised pod world size (parent only)")
    ap.add_argument("--devices-per-proc", type=int, default=1,
                    help="forced CPU devices per supervised child")
    ap.add_argument("--max-restarts", type=int, default=3,
                    help="supervisor restart budget before failing loudly")
    ap.add_argument("--heartbeat-deadline-s", type=float, default=60.0,
                    help="supervisor declares a hang when a child's newest "
                         "heartbeat is older than this")
    ap.add_argument("--step-deadline-s", type=float, default=None,
                    help="in-process StepWatchdog deadline per training "
                         "step (a hung step exits 44 for the supervisor)")
    ap.add_argument("--run-dir", default=None,
                    help="supervisor state dir (incidents.jsonl, heartbeats,"
                         " child logs); default <ckpt-dir>/supervisor")
    args = ap.parse_args()

    if args.supervised:
        import sys

        from repro.resilience import FaultPlan, PodSupervisor, SupervisorConfig

        # children run THIS command minus --supervised (plus --distributed
        # and --elastic: a degraded relaunch is a cross-host-count restore)
        child = [sys.executable, "-m", "repro.launch.train"] + [
            a for a in sys.argv[1:] if a != "--supervised"
        ]
        for needed in ("--distributed", "--elastic"):
            if needed not in child:
                child.append(needed)
        run_dir = args.run_dir or os.path.join(args.ckpt_dir, "supervisor")
        sup = PodSupervisor(
            child,
            SupervisorConfig(
                n_procs=args.nprocs,
                devices_per_proc=args.devices_per_proc,
                heartbeat_deadline_s=args.heartbeat_deadline_s,
                max_restarts=args.max_restarts,
            ),
            run_dir,
            # adopt a chaos plan armed on the parent (REPRO_FAULT_PLAN):
            # the supervisor arms it for attempt 0 and strips it from
            # relaunches, so an injected fault can't re-fire forever
            fault_plan=FaultPlan.from_env(),
            env={"PYTHONPATH": os.environ.get("PYTHONPATH", "")},
        )
        summary = sup.run()
        print(
            f"supervised pod done: attempts={summary['attempts']} "
            f"restarts={summary['restarts']} "
            f"final world={summary['world_size_final']} "
            f"incidents={summary['incidents_path']}"
        )
        return

    # the supervisor parent above compiles nothing and stays off jax
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    if args.distributed:
        from repro.launch.multihost import initialize_distributed

        initialize_distributed(
            args.coordinator, args.num_processes, args.process_id
        )
        import jax

        print(f"distributed: process {jax.process_index()}/"
              f"{jax.process_count()}, {len(jax.devices())} global devices")

    if args.arch == "mace_cfm":
        from repro.train.train_loop import Trainer

        extra = {}
        if args.engine is not None:
            extra["engine"] = args.engine
        if args.n_ranks is not None:
            extra["n_ranks"] = args.n_ranks
        if args.n_nodes is not None:
            extra["n_nodes"] = args.n_nodes
        if args.distributed and args.engine is None:
            import jax

            if jax.process_count() > 1:
                extra["engine"] = "multihost"
                extra.setdefault("n_nodes", jax.process_count())
                extra.setdefault("n_ranks", len(jax.devices()))
        cfg, tcfg, ds = mace_cfm_run(
            args.reduced,
            ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
            compress_grads=args.compress_grads, elastic=args.elastic,
            step_deadline_s=args.step_deadline_s, **extra,
        )
        tr = Trainer(cfg, tcfg, ds, seed=0)
        if tr.maybe_restore():
            print(f"resumed at step {tr.global_step}")
        out = tr.train(n_epochs=10**9, max_steps=args.steps)
        print(f"done: {len(out['history'])} steps, "
              f"final loss {out['history'][-1]['loss']:.4f}")
    else:
        # LM path: reuse the example driver (balanced sequence packing etc.)
        import sys

        sys.argv = ["lm_pretrain", "--arch", args.arch, "--steps", str(args.steps)]
        from examples import lm_pretrain  # type: ignore

        lm_pretrain.main()


if __name__ == "__main__":
    main()
