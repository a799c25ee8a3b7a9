"""Compile the Pallas kernels for a described v5e chip at MACE CFM widths.

Interpret-mode tests cannot see what the chip's compiler refuses: tiles not
aligned to the layout, or more scoped VMEM than a kernel may use (the
interaction backward at 128 channels and a 32x128 tile needs 16.79 MB
against Mosaic's 16 MiB default).  These tests compile each kernel, forward
and backward, with ``interpret=False`` for one chip of a ``v5e:2x2``
topology described in this process, at ``configs/mace_cfm.py`` widths
(k = 128, ``sh_lmax`` 3, correlation 2) and the tiles the Trainer uses on a
TPU, on the full-size bin's shapes (``launch.train.mace_cfm_run``): the
grid matters too, since the compiler may place a small operand whole in
VMEM, where it counts against the kernel's scoped budget.  Nothing runs.

The model's loss, compiled the same way at a small bin with the named
scopes in place, keeps the kernels' names in its Mosaic custom calls, so the
benchmark's rooflines (``bench/benchlib/trace.kernel_name``) still find them.
So does its parameter gradient, whose second-order pass runs the
interaction on its own kernels and holds no per-edge ``[E, k, nnz]``
CG-contribution tensor.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
import dataclasses
import os
import re
import sys

import numpy as np

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.mace_cfm import CONFIG
from repro.core.channelwise_tp import build_tp_tables
from repro.core.symmetric_contraction import build_symcon_tables
from repro.data.collate import BinShape
from repro.kernels import autotune
from repro.kernels.channelwise_tp.kernel import (
    tp_bwd_pallas_raw,
    tp_scatter_pallas_raw,
)
from repro.kernels.symmetric_contraction.kernel import (
    _group_entries,
    symcon_bwd_pallas_raw,
    symcon_pallas_raw,
)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "bench"))
from benchlib.trace import kernel_name  # noqa: E402


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from the
    # persistent cache; keep these compiles out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(fn, one_chip, *shapes):
    args = [
        jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes
    ]
    return jax.jit(fn).lower(*args).compile().as_text()


def _full_size_bin(which: str) -> BinShape:
    """The full-size run's bin on a TPU, with the interaction tile of the
    tuning table's decision or the default tile that the forced-Pallas run
    uses."""
    from repro.launch.train import mace_cfm_run

    _, tcfg, _ = mace_cfm_run()
    tile = tcfg.block_n, tcfg.block_e
    if which == "autotuned":
        _, decisions = autotune.resolve_mace_config(
            dataclasses.replace(CONFIG, impl="auto", interaction_impl="auto"),
            capacity=tcfg.capacity, edge_factor=tcfg.edge_factor,
            platform="tpu",
        )
        d = decisions["interaction"]
        assert d.impl == "pallas" and d.bwd_impl == "pallas", d.describe()
        tile = d.block_n, d.block_e
    return BinShape.for_capacity(
        tcfg.capacity, tcfg.edge_factor, tcfg.max_graphs,
        block_n=tile[0], block_e=tile[1],
    )


def _interaction_operands(layer: int, which: str):
    spec = CONFIG.interaction_spec_at(layer).tp
    shape = _full_size_bin(which)
    n_tiles, block_n, block_e = (
        shape.blocking_tiles, shape.block_n, shape.block_e
    )
    E_p = n_tiles * block_e
    k = CONFIG.channels
    f32, i32 = jnp.float32, jnp.int32
    edge = [
        ((E_p, spec.y_spec.dim), f32),
        ((E_p, spec.h_spec.dim, k), f32),
        ((E_p, spec.n_paths, k), f32),
        ((E_p, 1), i32),
        ((E_p, 1), f32),
    ]
    kw = dict(n_atom_tiles=n_tiles, block_n=block_n, block_e=block_e,
              interpret=False)
    g = ((n_tiles * block_n, spec.out_spec.dim, k), f32)
    return spec, build_tp_tables(spec), edge, g, kw


@pytest.mark.parametrize("which", ["autotuned", "default"])
@pytest.mark.parametrize("layer", [0, 1])
def test_interaction_fwd_compiles_for_v5e(one_chip, layer, which):
    spec, tables, edge, _, kw = _interaction_operands(layer, which)
    text = _compile(
        lambda *a: tp_scatter_pallas_raw(*a, spec, tables, **kw,
                                         name="interaction_fwd"),
        one_chip, *edge,
    )
    assert "tpu_custom_call" in text and "interaction_fwd" in text


@pytest.mark.parametrize("which", ["autotuned", "default"])
@pytest.mark.parametrize("layer", [0, 1])
def test_interaction_bwd_compiles_for_v5e(one_chip, layer, which):
    """Regression test for the scoped-VMEM overrun of the backward."""
    spec, tables, edge, g, kw = _interaction_operands(layer, which)
    text = _compile(
        lambda *a: tp_bwd_pallas_raw(*a, spec, tables, **kw,
                                     name="interaction_bwd"),
        one_chip, g, *edge,
    )
    assert "tpu_custom_call" in text and "interaction_bwd" in text


def _symcon_operands():
    spec = CONFIG.symcon_spec()
    tables = build_symcon_tables(spec)
    _, p_total = _group_entries(spec, tables)
    N = -(-_full_size_bin("default").max_nodes // 32) * 32
    k, f32 = CONFIG.channels, jnp.float32
    a = ((N, spec.in_spec.dim, k), f32)
    w = ((N, p_total, k), f32)
    g = ((N, spec.out_spec.dim, k), f32)
    return spec, tables, a, w, g


def test_symcon_fwd_compiles_for_v5e(one_chip):
    spec, tables, a, w, _ = _symcon_operands()
    text = _compile(
        lambda A, W: symcon_pallas_raw(A, W, spec, tables, interpret=False),
        one_chip, a, w,
    )
    assert "tpu_custom_call" in text and "symcon_fwd" in text


def test_symcon_bwd_compiles_for_v5e(one_chip):
    spec, tables, a, w, g = _symcon_operands()
    text = _compile(
        lambda A, W, G: symcon_bwd_pallas_raw(A, W, G, spec, tables,
                                              interpret=False),
        one_chip, a, w, g,
    )
    assert "tpu_custom_call" in text and "symcon_bwd" in text


def _compile_small_loss(one_chip, monkeypatch, grad: bool):
    """The model's forces-in-loss objective on a small bin at full widths,
    all-Pallas, compiled for the chip: its value (forward and force pass)
    or its parameter gradient (adding the second-order pass).  Returns the
    HLO text, the bin and the config."""
    from repro.core.mace import init_mace, weighted_loss
    from repro.data.collate import collate_bin
    from repro.data.molecules import SyntheticCFMDataset

    # the kernels compile for the chip instead of interpreting
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(CONFIG, impl="pallas", interaction_impl="pallas")
    shape = BinShape.for_capacity(64, 16, 8, block_n=cfg.interaction_block_n)
    ds = SyntheticCFMDataset(4, seed=0, max_atoms=16)
    batch = collate_bin([ds.get(i) for i in range(4)], shape,
                        with_blocking=True)
    params = jax.eval_shape(lambda: init_mace(jax.random.PRNGKey(0), cfg))
    sds = lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                         sharding=one_chip)

    def loss(p, b):
        return weighted_loss(p, cfg, b, shape.max_graphs, 0.0, 100.0)[0]

    text = jax.jit(jax.grad(loss) if grad else loss).lower(
        jax.tree.map(sds, params), jax.tree.map(sds, batch)
    ).compile().as_text()
    return text, shape, cfg


def _kernel_names(text):
    calls = [line.split(" = ")[0].split()[-1].lstrip("%")
             for line in text.splitlines()
             if "tpu_custom_call" in line and "custom-call(" in line]
    return {kernel_name(c) for c in calls}


def test_loss_kernel_calls_keep_their_names_for_v5e(one_chip, monkeypatch):
    """Forward and force pass of the model's loss under its named scopes:
    every Mosaic call reduces to one of the four kernels' names."""
    text, _, _ = _compile_small_loss(one_chip, monkeypatch, grad=False)
    assert _kernel_names(text) == {
        "symcon_fwd", "symcon_bwd", "interaction_fwd", "interaction_bwd"}


def test_loss_param_grad_second_order_on_kernels_for_v5e(one_chip,
                                                         monkeypatch):
    """The parameter gradient of the forces-in-loss objective, the
    second-order pass included: the interaction's second order runs on its
    own kernels, so no per-edge CG-contribution tensor ``f32[E, k, nnz]``
    (the XLA twin's largest temporary, in any axis order) is left, and
    every Mosaic call still reduces to the four kernels' names."""
    text, shape, cfg = _compile_small_loss(one_chip, monkeypatch, grad=True)
    assert _kernel_names(text) == {
        "symcon_fwd", "symcon_bwd", "interaction_fwd", "interaction_bwd"}
    contrib = {
        tuple(sorted((shape.max_edges, cfg.channels, len(build_tp_tables(
            cfg.interaction_spec_at(layer).tp).val))))
        for layer in range(cfg.n_interactions)
    }
    f32_shapes = {
        tuple(sorted(int(d) for d in dims.split(",")))
        for dims in re.findall(r"\bf32\[([0-9,]+)\]", text)
    }
    assert not contrib & f32_shapes, contrib & f32_shapes
