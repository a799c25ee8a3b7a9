"""The training driver's stall note on hand-built windows: the median step,
the steps over twice it with their summed excess, and the longest step."""
import _paths
import pytest


def _driver():
    run = _paths.bench_run()
    return run.load_module(_paths.BENCH / "drivers" / "train.py", "bench_driver_train")


@pytest.mark.parametrize("ms, count, excess, longest", [
    pytest.param([200.0, 202.0, 199.0, 201.0, 203.0], 0, 0.0, 4, id="no_stall"),
    pytest.param([200.0, 201.0, 1400.0, 199.0, 202.0], 1, 1199.0, 2, id="one_stall"),
    pytest.param([900.0, 500.0, 200.0, 200.0, 199.0, 201.0, 200.0], 2, 1000.0, 0,
                 id="stalls_at_first_step"),
])
def test_stalls_of_a_window(ms, count, excess, longest):
    driver = _driver()
    med = sorted(ms)[len(ms) // 2]
    s = driver.stalls(ms)
    assert s["median_ms"] == pytest.approx(med)
    assert s["count"] == count
    assert s["excess_ms"] == pytest.approx(excess)
    assert (s["longest"], s["longest_ms"]) == (longest, max(ms))
    note = driver.stall_note(ms, [(i + 7, 134) for i in range(len(ms))])
    assert note.startswith("note: stalls:")
    assert f"{count} steps over twice it" in note
    assert f"longest step {longest} of {len(ms)}" in note
    assert f"step {longest + 7} of 134 in its epoch" in note
