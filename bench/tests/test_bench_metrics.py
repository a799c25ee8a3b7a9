"""Each per-layer metric's reader on the recorded two-step chip trace: a
share of a roofline or a peak lies in (0, 100], and a reader with nothing
to read returns nothing."""
import gzip
import json

import _paths
import pytest

from benchlib import peaks, trace, work
from reference.mace import Sizes

METRICS = ["atom_pad_share", "edge_pad_share", "host_input_ms", "symcon_roofline",
           "interaction_roofline", "device_idle_share", "step_mfu"]
SIZES = Sizes(89, 128, (0, 1), 3, (0, 1, 2, 3), 2, 2, 4.5, 8, (64, 64, 64), 16, 14.0)


def _reader(name):
    run = _paths.bench_run()
    return run.load_module(_paths.BENCH / "metrics" / f"{name}.py", f"metric_{name}")


def _ctx(device_ops=None):
    with gzip.open(_paths.BENCH / "tests" / "data" / "cfm_two_steps.json.gz", "rt") as f:
        rec = json.load(f)
    ops = rec["device_ops"] if device_ops is None else device_ops
    t = trace.from_events({d: [tuple(o) for o in v] for d, v in ops.items()},
                          [tuple(h) for h in rec["host_spans"]])
    steps = [{"ms": 470.0, "atoms": 1390, "edges": 24100, "graphs": 7},
             {"ms": 471.0, "atoms": 1402, "edges": 24300, "graphs": 6}]
    return {"trace": t, "steps": steps, "sizes": SIZES, "slots": (1408, 45056),
            "peaks": peaks.peaks_for("TPU v5 lite"), "chips": 1,
            "host_spans": {"bench.fetch_batch": [0.02, 0.03]}, "work": work}


@pytest.mark.parametrize("name", METRICS)
def test_reader_gives_a_share_in_range(name):
    value = _reader(name).read(_ctx())
    assert value is not None and value > 0
    if name.endswith("_roofline") or "mfu" in name or name.endswith("_share"):
        assert value <= 100.0


def test_edge_padding_counts_real_edges_against_slots():
    assert _reader("edge_pad_share").read(_ctx()) == pytest.approx(
        100 * (1 - 48400 / (2 * 45056)))


def test_kernel_rooflines_are_silent_without_their_kernels():
    ctx = _ctx()
    no_kernels = {d: [o for o in ops if "symcon" not in o[0] and "interaction" not in o[0]]
                  for d, ops in ctx["trace"].device_ops.items()}
    ctx = _ctx(no_kernels)
    assert _reader("symcon_roofline").read(ctx) is None
    assert _reader("interaction_roofline").read(ctx) is None


def test_host_input_is_silent_without_its_span():
    ctx = dict(_ctx(), host_spans={"missing": ["bench.fetch_batch"]})
    assert _reader("host_input_ms").read(ctx) is None
    assert _reader("host_input_ms").read(_ctx()) == pytest.approx(25.0)
