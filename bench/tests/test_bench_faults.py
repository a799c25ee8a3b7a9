"""The check behind ``correct`` fails what it must: a run of the harness on
the CPU at a small size, with the chip check skipped, comes out correct
when the program is sound and not correct when the timed path is broken
underneath (a step that returns its state unchanged; half of each bin left
out of the loss) or when the reference computed in bfloat16, the control,
takes the program's place."""
import json
import types

import _paths
import jax

CELL = "cfm_forces_t3"
CONFIG = {
    "program": None,
    "model": {"n_species": 10, "channels": 8, "hidden_ls": [0, 1], "sh_lmax": 2,
              "a_ls": [0, 1, 2], "correlation": 2, "n_interactions": 2,
              "r_max": 4.5, "num_bessel": 8, "radial_mlp": [16, 16],
              "readout_mlp": 8, "avg_num_neighbors": 8.0},
    "trainer": {"capacity": 64, "edge_factor": 24, "max_graphs": 16,
                "impl": "fused", "interaction_impl": "fused",
                "energy_weight": 0.0},
}
TRAFFIC = {"driver": "train",
           "mixture": [["water_clusters", 1.0, 4, 20, "amorphous", 0.8]],
           "graphs": 48, "size_seed": 3, "order_seed": 4, "n_species": 10,
           "warmup_steps": 1}


def _limits():
    return json.loads((_paths.BENCH / "limits" / f"{CELL}.json").read_text())["limits"]


def _execute(seed=2**33 + 5):
    run = _paths.bench_run()
    bench = json.loads((_paths.ROOT / "BENCHMARK.json").read_text())
    cell = run.Cell("small", 1, CONFIG, TRAFFIC, _limits(), bench["end_to_end"], [])
    args = types.SimpleNamespace(seed=seed, seconds=0.5, trace=0)
    return run.execute(cell, args, jax.devices()[:1], None)


def test_sound_program_is_correct():
    line = _execute()
    assert line["correct"], line["checks"]
    assert line["metrics"]["atoms_per_s"]["value"] > 0


def test_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    from repro.train.engine import SequentialEngine

    step = SequentialEngine.step

    def unchanged(self, params, opt_state, ef_state, batches, step_idx):
        *_, metrics = step(self, params, opt_state, ef_state, batches, step_idx)
        return params, opt_state, ef_state, metrics

    monkeypatch.setattr(SequentialEngine, "step", unchanged)
    line = _execute()
    assert not line["correct"]
    assert line["checks"]["update_norm_gap"]["value"] > 0.5


def test_half_of_each_bin_left_out_is_not_correct(monkeypatch):
    from repro.train.engine import SequentialEngine

    collate = SequentialEngine.collate

    def half(self, mols_per_rank, shape):
        return collate(self, [m[: max(1, len(m) // 2)] for m in mols_per_rank], shape)

    monkeypatch.setattr(SequentialEngine, "collate", half)
    assert not _execute()["correct"]


def test_bfloat16_control_is_not_correct():
    """The reference in bfloat16 in the program's place fails a limit."""
    from benchlib import checks
    from benchlib.generator import Pool

    driver = _driver()
    mace_cfg, tcfg, _ = driver.program_config(CONFIG)
    pool = Pool(TRAFFIC, mace_cfg.r_max, seed=11)
    b = driver.build(mace_cfg, tcfg, pool, seed=11, order_seed=TRAFFIC["order_seed"])
    bins = [[bb] for bb in b.trainer.sampler.bins_for_epoch(0)[:driver.CHECK_STEPS]]
    want = driver.reference_readings(b, pool, bins, 11)
    control = driver.reference_readings(b, pool, bins, 11, dtype="bfloat16")
    verdicts = checks.compare(control, want, _limits())
    assert not all(v["ok"] for v in verdicts), verdicts


def _driver():
    run = _paths.bench_run()
    return run.load_module(_paths.BENCH / "drivers" / "train.py", "bench_driver_train")


def test_every_seed_trains_the_same_sequence_of_bin_sizes():
    """The traffic file fixes the sizes and the bins' epoch order: two run
    seeds train bins of the same sizes in the same order."""
    from benchlib.generator import Pool

    driver = _driver()
    mace_cfg, tcfg, _ = driver.program_config(CONFIG)
    seqs = []
    for seed in (2**33 + 7, 2**33 + 8):
        pool = Pool(TRAFFIC, mace_cfg.r_max, seed=seed)
        b = driver.build(mace_cfg, tcfg, pool, seed, TRAFFIC["order_seed"])
        seqs.append([[sorted(pool.sizes[bb].tolist())
                      for bb in b.trainer.sampler.bins_for_epoch(epoch)]
                     for epoch in (0, 1)])
    assert seqs[0] == seqs[1]
    assert seqs[0][0] != seqs[0][1]
