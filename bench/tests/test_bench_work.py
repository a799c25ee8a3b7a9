"""Work counts and peaks: the symmetric contraction counts as the
program's ``symcon_flops``, the interaction from the TP tables, and neither
depends on which implementation runs."""

import _paths  # noqa: F401
import pytest

from benchlib import peaks, work
from reference.mace import Sizes


def _cfg(correlation, **kw):
    from repro.core.mace import MaceConfig

    return MaceConfig(n_species=89, channels=128, hidden_ls=(0, 1), sh_lmax=3,
                      a_ls=(0, 1, 2, 3), correlation=correlation,
                      n_interactions=2, r_max=4.5, num_bessel=8,
                      avg_num_neighbors=14.0, **kw)


@pytest.mark.parametrize("correlation", [2, 3])
def test_symcon_count_is_the_programs(correlation):
    from repro.core.symmetric_contraction import symcon_flops

    cfg = _cfg(correlation)
    s = Sizes.of(cfg)
    for n_atoms in (1, 448, 1408):
        assert work.symcon_ops(s, n_atoms) == symcon_flops(
            cfg.symcon_spec(), n_atoms, 128)


def test_interaction_count_follows_the_tp_tables():
    from repro.core.channelwise_tp import build_tp_tables

    cfg = _cfg(2)
    s = Sizes.of(cfg)
    for layer in range(2):
        tables = build_tp_tables(cfg.tp_spec_at(layer))
        nnz, d_out = len(tables.val), tables.dim_out
        assert work.tp_nnz(s.sh_ls, s.h_ls(layer), s.a_ls) == nnz
        ops, _ = work.interaction_call(s, layer, 100, 1700, backward=False)
        assert ops == 1700 * 128 * (4 * nnz + 2 * d_out)


def test_counts_do_not_depend_on_the_implementation():
    counts = []
    for impl in ("ref", "fused", "pallas"):
        s = Sizes.of(_cfg(2, impl=impl, interaction_impl=impl))
        counts.append((work.symcon_call(s, 1000, True),
                       work.interaction_call(s, 1, 1000, 17000, False),
                       work.forward_flops(s, 1000, 17000)))
    assert counts[0] == counts[1] == counts[2]


def test_bytes_count_each_operand_once():
    s = Sizes.of(_cfg(2))
    ops, nbytes = work.symcon_call(s, 10, backward=False)
    paths = work.symcon_paths(s.a_ls, s.hidden_ls, 2)
    assert nbytes == 4 * (10 * 128 * 16 + 10 * 128 * 4 + 89 * 128 * paths) + 4 * 10
    _, bwd_bytes = work.symcon_call(s, 10, backward=True)
    assert bwd_bytes == 4 * (10 * 128 * 4 + 2 * 10 * 128 * 16
                             + 2 * 89 * 128 * paths) + 4 * 10


def test_training_step_is_nine_forwards():
    s = Sizes.of(_cfg(2))
    assert work.train_step_flops(s, 1400, 24000) == pytest.approx(
        9 * work.forward_flops(s, 1400, 24000))


def test_least_time_names_its_bound():
    p = peaks.peaks_for("TPU v5 lite")
    assert work.least_time(197e12, 0, p) == (pytest.approx(1.0), "compute")
    assert work.least_time(0, 819e9, p) == (pytest.approx(1.0), "memory")


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("cpu")
