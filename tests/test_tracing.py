"""The training program's own spans, step counters and named scopes.

A small MACE trains three steps under the JAX profiler on the CPU: every
span ``repro.tracing`` names is in the trace, each inside its parent on the
same thread, and each ``train.step`` carries the real atoms of the bins the
sampler gave it and no compile.  With no profiler running the counters are
not built.  The SPMD engine's step, compiled on a forced
2-device CPU mesh, carries the model's and the engine's named scopes in its
ops' metadata, the second-order pass among them.
"""
import glob
import json
import os
import re
import subprocess
import sys

import numpy as np

from repro.core.mace import MaceConfig
from repro.data.molecules import SyntheticCFMDataset
from repro.train.train_loop import Trainer, TrainerConfig

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, os.path.join(ROOT, "bench"))

from benchlib import program_trace  # noqa: E402

# child -> parent, as repro.tracing documents them
PARENT = {
    "train.wait_batch": "train.step",
    "data.fetch": "train.wait_batch",
    "data.materialise": "data.fetch",
    "data.collate": "data.fetch",
    "data.blocking": "data.collate",
    "data.to_device": "data.fetch",
    "engine.step": "train.step",
    "engine.grad": "engine.step",
    "engine.grad_wait": "engine.step",
    "engine.stack": "engine.step",
    "engine.finalize": "engine.step",
    "train.ema_update": "train.step",
    "train.metrics": "train.step",
    "train.checkpoint": "train.step",
}
STEPS = 3


def _host_spans(path):
    """Per host thread: the program's spans as (name, start, end, args)."""
    from jax.profiler import ProfileData

    lines = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                      dict(e.stats)) for e in line.events
                     if e.name.startswith(program_trace.SPAN_PREFIXES)]
            if spans:
                lines.append(spans)
    return lines


def test_program_spans_nest_and_step_counters_match_the_sampler(tmp_path):
    import jax

    cfg = MaceConfig(n_species=10, channels=4, hidden_ls=(0, 1), sh_lmax=2,
                     a_ls=(0, 1, 2), correlation=2, n_interactions=2,
                     avg_num_neighbors=8.0, impl="fused",
                     interaction_impl="pallas")  # consumes the edge blocking
    ds = SyntheticCFMDataset(24, seed=0, max_atoms=24)
    tcfg = TrainerConfig(capacity=64, edge_factor=16, max_graphs=8,
                         ckpt_dir=str(tmp_path / "ckpt"), ckpt_every=STEPS)
    tr = Trainer(cfg, tcfg, ds, seed=0)
    tr.train(n_epochs=1, max_steps=1)  # compiles outside the trace
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        tr.train(n_epochs=1, max_steps=1 + STEPS)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "trace" / "**" / "*.xplane.pb"),
                     recursive=True)[0]
    lines = _host_spans(path)
    names = {s[0] for spans in lines for s in spans}
    assert names == set(PARENT) | {"train.step"}
    for spans in lines:
        for name, start, end, _ in spans:
            if name in PARENT:
                assert any(p == PARENT[name] and ps <= start and end <= pe
                           for p, ps, pe, _ in spans), name

    steps = sorted((s for spans in lines for s in spans
                    if s[0] == "train.step" and "atoms" in s[3]),
                   key=lambda s: s[3]["step_num"])
    bins = tr.sampler.bins_for_epoch(0)
    assert [s[3]["step_num"] for s in steps] == [1, 2, 3]
    for (_, _, _, args), b in zip(steps, bins[1:]):
        assert json.loads(args["atoms"]) == [int(ds.sizes[b].sum())]
        assert args["compiles"] == 0
    # the engine's telemetry reads the same host counts
    assert tr.engine.telemetry.load_matrix()[-STEPS:, 0].tolist() == [
        float(ds.sizes[b].sum()) for b in bins[1:1 + STEPS]]


def test_span_counters_are_not_built_without_a_profiler():
    from repro import tracing

    def counters():
        raise AssertionError("built with no profiler running")

    with tracing.span("train.step", step_num=0) as step:
        assert not step.enabled
        step.annotate(counters)
    assert step.seconds > 0


SCOPES_SCRIPT = r"""
import os, sys, json, re
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
sys.path.insert(0, sys.argv[1])
from benchlib import program_trace
from repro.core.mace import MaceConfig
from repro.data.molecules import SyntheticCFMDataset
from repro.train.train_loop import Trainer, TrainerConfig

cfg = MaceConfig(n_species=10, channels=4, hidden_ls=(0, 1), sh_lmax=2,
                 a_ls=(0, 1, 2), correlation=2, n_interactions=2,
                 avg_num_neighbors=8.0, impl="fused")
ds = SyntheticCFMDataset(16, seed=0, max_atoms=24)
tr = Trainer(cfg, TrainerConfig(capacity=64, edge_factor=16, max_graphs=8,
                                engine="shard_map", n_ranks=2), ds, seed=0)
batch, _ = tr._fetch_batch(tr.sampler.bins_for_epoch(0)[:2])
step = jax.numpy.asarray(0)
text = tr.engine._step_fn.lower(tr.params, tr.opt_state, tr.ef_state, batch,
                                tr._place(step)).compile().as_text()
names = re.findall(r'op_name="([^"]*)"', text)
kinds = sorted({program_trace.classify(n) for n in names},
               key=lambda k: (str(k[0]), k[1]))
print("RESULT " + json.dumps({"kinds": kinds,
                              "collectives": re.findall(
                                  r'all-reduce[^\n]*op_name="([^"]*)"', text)}))
"""


def test_spmd_step_ops_carry_scopes_and_passes():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", SCOPES_SCRIPT, os.path.join(ROOT, "bench")],
        capture_output=True, text=True, timeout=600, env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.split("RESULT ")[-1])
    kinds = {tuple(k) for k in out["kinds"]}
    scopes = {s for s, _ in kinds}
    assert {"interaction", "symcon", "optimizer", "allreduce", "edge_geometry",
            "radial", "linear", "skip", "readout", "loss"} <= scopes
    # forward, first-order and second-order passes of the model's ops
    for scope in ("interaction", "symcon"):
        assert {p for s, p in kinds if s == scope} == {0, 1, 2}, scope
    assert {p for s, p in kinds if s in ("optimizer", "allreduce")} == {0}
    assert out["collectives"] and all(
        program_trace.classify(n)[0] == "allreduce"
        for n in out["collectives"])
