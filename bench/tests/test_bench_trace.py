"""Trace reduction on a hand-built trace: busy union, idle share, kernel
time, collective time and the labelled idle gaps."""
import _paths
import pytest

from benchlib import trace as tr

MS = 1_000_000


def _trace():
    dev0 = [("fusion.1", 0, 10 * MS), ("symcon_fwd", 5 * MS, 12 * MS),
            ("interaction_bwd.3", 20 * MS, 30 * MS),
            ("all-reduce.1", 28 * MS, 34 * MS),
            ("all-reduce-start", 40 * MS, 44 * MS)]
    dev1 = [("fusion.1", 0, 50 * MS), ("symcon_fwd.2", 60 * MS, 70 * MS)]
    host = [("bench.window", 0, 100 * MS),
            ("bench.fetch_batch", 12 * MS, 19 * MS),
            ("bench.engine_step", 10 * MS, 60 * MS),
            ("bench.ema_update", 70 * MS, 100 * MS)]
    return tr.from_events({"/device:TPU:0": dev0, "/device:TPU:1": dev1},
                          host)


def test_window_is_the_window_span():
    t = _trace()
    assert t.window == (0, 100 * MS)
    assert t.window_s == pytest.approx(0.1)


def test_busy_union_and_idle_share():
    t = _trace()
    # dev0: [0,12] + [20,34] + [40,44] = 30 ms; dev1: [0,50] + [60,70] = 60 ms
    assert tr.busy_s(t, "/device:TPU:0") == pytest.approx(0.030)
    assert tr.busy_s(t, "/device:TPU:1") == pytest.approx(0.060)
    assert tr.mean_busy_s(t) == pytest.approx(0.045)
    assert tr.idle_share(t) == pytest.approx(0.55)


def test_union_clips_to_the_window():
    assert tr.union([(-5, 5), (4, 8), (20, 30)], (0, 25)) == [(0, 8), (20, 25)]


def test_kernel_time_by_name_ignores_numeric_suffixes():
    t = _trace()
    assert tr.kernel_time_s(t, ["symcon_fwd"]) == {"symcon_fwd": pytest.approx(0.017)}
    assert tr.kernel_counts(t, ["symcon_fwd", "interaction_bwd", "symcon_bwd"]) == {
        "symcon_fwd": 2, "interaction_bwd": 1, "symcon_bwd": 0}
    assert not tr.is_kernel("symcon_fwd_fusion", "symcon_fwd")


def test_collective_time_and_exposed_part():
    per_dev = tr.collective_s(_trace())
    total, exposed = per_dev["/device:TPU:0"]
    # all-reduce [28,34] overlaps compute [20,30] for 2 ms; the start op
    # [40,44] overlaps nothing
    assert total == pytest.approx(0.010)
    assert exposed == pytest.approx(0.008)
    assert per_dev["/device:TPU:1"] == (0.0, 0.0)


def test_top_ops_per_device():
    top = dict(tr.top_ops(_trace()))
    assert top["fusion"] == pytest.approx((0.010 + 0.050) / 2)
    assert top["symcon_fwd"] == pytest.approx(0.017 / 2)


def test_idle_time_is_summed_by_the_innermost_host_span():
    gaps = dict(tr.idle_gaps(_trace()))
    # dev0 idles [12,20] in the fetch (inside the step), [34,40] in the
    # step and [44,100] in the EMA update; dev1 [50,60] in the step and
    # [70,100] in the EMA update; per device: halve the sums
    assert gaps["bench.ema_update x2"] == pytest.approx((0.056 + 0.030) / 2)
    assert gaps["bench.engine_step x2"] == pytest.approx((0.006 + 0.010) / 2)
    assert gaps["bench.fetch_batch x1"] == pytest.approx(0.008 / 2)
    assert list(gaps)[0] == "bench.ema_update x2"


def test_gap_with_no_host_span_is_unattributed():
    t = tr.from_events({"/device:TPU:0": [("fusion", 0, 10), ("fusion", 90, 100)]},
                       [("bench.window", 0, 100)])
    assert tr.idle_gaps(t) == [(tr.UNATTRIBUTED + " x1", pytest.approx(80e-9))]


def test_window_without_span_is_the_extent_of_all_events():
    t = tr.from_events({"/device:TPU:0": [("a", 5, 10), ("b", 30, 40)]}, [])
    assert t.window == (5, 40)
    assert tr.idle_share(t) == pytest.approx(1 - 15 / 35)


def test_kernel_names_inside_transformations():
    assert tr.op_name("%jvp_jvp_symcon_fwd__.2 = f32[1408,4,128]{2,1,0:T(4,128)} "
                      "custom-call(...)") == "jvp_jvp_symcon_fwd__.2 f32[1408,4,128]"
    assert tr.base_name("fusion.188 f32[1408,128,86]") == "fusion f32[1408,128,86]"
    assert tr.kernel_name("jvp_jvp_symcon_fwd__.2 f32[1408,4,128]") == "symcon_fwd"
    assert tr.kernel_name("jvp_transpose_jvp_jvp_interaction_bwd____.3") == "interaction_bwd"
    assert tr.kernel_name("transpose_jvp_jvp_symcon_bwd___.2") == "symcon_bwd"
    assert tr.kernel_name("fusion.188") == "fusion"


def test_leaf_ops_drop_the_loop_that_holds_its_body():
    ops = [("while.1", 0, 100), ("dus.1", 10, 20), ("dus.1", 30, 40), ("copy", 100, 110)]
    assert [o[0] for o in tr.leaf_ops(ops)] == ["dus.1", "dus.1", "copy"]


def test_recorded_chip_trace_of_two_steps():
    """Two training steps of MACE CFM on one v5e: each step calls symcon and the
    interaction forward once per layer and their backward twice per layer."""
    import gzip
    import json

    with gzip.open(_paths.BENCH / "tests" / "data" / "cfm_two_steps.json.gz", "rt") as f:
        rec = json.load(f)
    t = tr.from_events({d: [tuple(o) for o in ops] for d, ops in rec["device_ops"].items()},
                       [tuple(h) for h in rec["host_spans"]])
    counts = tr.kernel_counts(t, ["symcon_fwd", "symcon_bwd", "interaction_fwd",
                                  "interaction_bwd"])
    assert counts == {"symcon_fwd": 4, "symcon_bwd": 8, "interaction_fwd": 4,
                      "interaction_bwd": 8}
    assert 0.0 < tr.idle_share(t) < 0.5
    assert tr.idle_gaps(t, 1)[0][0].startswith("bench.")
    times = tr.kernel_time_s(t, ["interaction_bwd"])
    assert 0.0 < times["interaction_bwd"] < t.window_s
    assert sum(s for _, s in tr.top_ops(t, 1000)) <= tr.mean_busy_s(t) + 1e-9


def test_longest_gaps_are_placed_in_the_window():
    gaps = tr.longest_gaps(_trace(), 2)
    assert gaps == [(pytest.approx(0.044), pytest.approx(0.056)),
                    (pytest.approx(0.070), pytest.approx(0.030))]
