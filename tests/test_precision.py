"""Mixed-precision kernel variants: the per-precision tolerance contract.

The bf16/fp8 pallas variants round *operand tile loads* to the reduced
dtype and accumulate in fp32 (``repro.kernels.precision.round_to``); the
hand-written backward kernels apply the same rounding.  Every second-order
pass stays fp32 at every setting: the interaction backward's own rule runs
its forward and backward kernels at fp32 (pinned bit for bit below), and
the symcon and TP-only backwards differentiate their fp32 XLA twins.

Tolerance contract (PRECISION_TOL): gradients and outputs are compared to
the fp32 ref oracle with the **L2 norm-relative** metric per tensor,

    ||got - want||_2 / ||want||_2  <=  PRECISION_TOL[precision]

not max-element relative error — per-element relative error is unbounded
at cancellation points (a near-zero fp32 gradient element keeps the full
bf16 rounding noise of its large addends), while the norm ratio measures
the actual perturbation of the update direction.  The bounds are
calibrated ceilings from the kernel matrix on CPU interpret mode, with
~2.5x headroom over the worst observed case (bf16 worst: TP grads ~0.020;
fp8 worst: symcon grads ~0.24 — fp8 e4m3 has a 3-bit mantissa, so a
relative drift approaching 0.4 is expected, and fp8 stays an emulation
contract rather than a training default).
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.channelwise_tp import TPSpec
from repro.core.interaction import InteractionSpec
from repro.core.irreps import lspec, sh_spec
from repro.core.mace import MaceConfig
from repro.core.symmetric_contraction import SymConSpec, init_symcon_weights
from repro.data.blocking import block_edges
from repro.kernels import registry
from repro.kernels.precision import (
    PRECISION_TOL,
    PRECISIONS,
    check_precision,
    l2_rel as _l2_rel,
    round_to,
)

# reduced precisions exercised by the parity matrix, as (precision, impl)
VARIANTS = [("bf16", "pallas_bf16"), ("fp8", "pallas_fp8")]

ISPEC = InteractionSpec(
    TPSpec(sh_spec(2), lspec(0, 1), lspec(0, 1, 2)),
    avg_num_neighbors=4.0,
    block_n=8,
)


def _assert_tree_close(got, want, precision):
    tol = PRECISION_TOL[precision]
    for i, (g, w) in enumerate(zip(jax.tree.leaves(got), jax.tree.leaves(want))):
        err = _l2_rel(g, w)
        assert err <= tol, (
            f"leaf {i}: L2 norm-relative error {err:.4g} exceeds the "
            f"{precision} contract {tol:g}"
        )


def _assert_tree_differs(got, ref):
    """The precision knob must be live: reduced-precision output is not
    bitwise fp32 output (a silently-ignored knob would pass every
    tolerance check)."""
    diffs = [
        float(np.abs(np.asarray(g) - np.asarray(w)).max())
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(ref))
    ]
    assert max(diffs) > 0.0, "reduced-precision path returned bitwise fp32"


# ---------------------------------------------------------------------------
# the rounding helper itself
# ---------------------------------------------------------------------------


def test_round_to_contract():
    x = jnp.linspace(-3.0, 3.0, 97, dtype=jnp.float32) * 1.7
    assert round_to(x, "fp32") is x  # identity, not a copy
    for prec, eps in (("bf16", 2 ** -8), ("fp8", 2 ** -2)):
        y = round_to(x, prec)
        assert y.dtype == jnp.float32  # rounds *through* the narrow dtype
        rel = np.abs(np.asarray(y) - np.asarray(x)) / np.maximum(np.abs(x), 1e-9)
        assert 0.0 < rel.max() <= eps
    with pytest.raises(ValueError):
        check_precision("fp16")
    assert [check_precision(p) for p in PRECISIONS] == list(PRECISIONS)


# ---------------------------------------------------------------------------
# registry capability surface
# ---------------------------------------------------------------------------


def test_registry_lists_precision_variants():
    for kind in ("symcon", "channelwise_tp", "interaction"):
        names = registry.available(kind)
        assert {"pallas_bf16", "pallas_fp8"} <= set(names)
        # the precision filter partitions the namespace
        assert registry.available(kind, precision="bf16") == ["pallas_bf16"]
        assert registry.available(kind, precision="fp8") == ["pallas_fp8"]
        assert "pallas_bf16" not in registry.available(kind, precision="fp32")
        caps = registry.capabilities(kind)
        assert caps["pallas"]["precision"] == "fp32"
        for prec in ("bf16", "fp8"):
            row = caps[f"pallas_{prec}"]
            # variants inherit the pallas deployment surface: TPU-native,
            # interpret-mode on cpu, hand-written backward
            assert row["precision"] == prec
            assert row["uses_pallas"] and row["has_custom_bwd"]
            assert "cpu" in row["interpret_only_on"]


# ---------------------------------------------------------------------------
# grad-parity matrix vs the fp32 ref oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("precision,impl", VARIANTS)
def test_symcon_precision_parity(precision, impl):
    spec = SymConSpec(lspec(0, 1, 2), lspec(0, 1), 2)
    key = jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(key, 3)
    N, k = 17, 4  # 17 atoms: ragged last tile exercises row padding
    A = jax.random.normal(k1, (N, k, spec.in_spec.dim), jnp.float32)
    species = jax.random.randint(k2, (N,), 0, 3)
    W = init_symcon_weights(k3, spec, 3, k)
    ref = registry.resolve("symcon", "ref", spec)
    var = registry.resolve("symcon", impl, spec)

    def loss(fn):
        return lambda a, w: jnp.sum(fn(a, species, w) ** 2)

    want_v, want_g = jax.value_and_grad(loss(ref), argnums=(0, 1))(A, W)
    got_v, got_g = jax.value_and_grad(loss(var), argnums=(0, 1))(A, W)
    _assert_tree_close([got_v], [want_v], precision)
    _assert_tree_close(got_g, want_g, precision)
    _assert_tree_differs(got_g, want_g)


@pytest.mark.parametrize("precision,impl", VARIANTS)
def test_tp_precision_parity(precision, impl):
    spec = TPSpec(sh_spec(2), lspec(0, 1), lspec(0, 1, 2))
    key = jax.random.PRNGKey(1)
    k1, k2, k3 = jax.random.split(key, 3)
    E, k = 48, 4
    Y = jax.random.normal(k1, (E, spec.y_spec.dim), jnp.float32)
    h = jax.random.normal(k2, (E, k, spec.h_spec.dim), jnp.float32)
    R = jax.random.normal(k3, (E, spec.n_paths, k), jnp.float32)
    ref = registry.resolve("channelwise_tp", "ref", spec)
    var = registry.resolve("channelwise_tp", impl, spec)

    def loss(fn):
        return lambda y, hh, r: jnp.sum(fn(y, hh, r) ** 2)

    want_v, want_g = jax.value_and_grad(loss(ref), argnums=(0, 1, 2))(Y, h, R)
    got_v, got_g = jax.value_and_grad(loss(var), argnums=(0, 1, 2))(Y, h, R)
    _assert_tree_close([got_v], [want_v], precision)
    _assert_tree_close(got_g, want_g, precision)
    _assert_tree_differs(got_g, want_g)


def _interaction_inputs(key, E, n_atoms, k, edge_keep=0.9):
    k1, k2, k3, k4, k5, k6 = jax.random.split(key, 6)
    Y = jax.random.normal(k1, (E, ISPEC.tp.y_spec.dim), jnp.float32)
    h = jax.random.normal(k2, (n_atoms, k, ISPEC.tp.h_spec.dim), jnp.float32)
    R = jax.random.normal(k3, (E, ISPEC.tp.n_paths, k), jnp.float32)
    senders = jax.random.randint(k4, (E,), 0, n_atoms)
    receivers = jax.random.randint(k5, (E,), 0, n_atoms)
    edge_mask = jax.random.bernoulli(k6, edge_keep, (E,))
    return Y, h, R, senders, receivers, edge_mask


def _blocking_arrays(receivers, edge_mask, n_atoms, block_e=16):
    b = block_edges(
        np.asarray(receivers), np.asarray(edge_mask), n_atoms,
        block_n=ISPEC.block_n, block_e=block_e,
    )
    return {
        "perm": jnp.asarray(b.perm, jnp.int32),
        "valid": jnp.asarray(b.valid),
        "local": jnp.asarray(b.local_rcv),
        "base": jnp.asarray(b.tile_base),
    }, b


def _interaction_grads(spec, impl, blocking, args):
    fn = registry.resolve("interaction", impl, spec)

    def loss(y, hh, r):
        return jnp.sum(fn(y, hh, r, *args[3:], blocking=blocking) ** 2)

    return jax.grad(loss, argnums=(0, 1, 2))(*args[:3])


@pytest.mark.parametrize("precision,impl", VARIANTS)
def test_interaction_precision_parity_masked_padded(precision, impl):
    """Full interaction op (fwd + hand-written bwd) vs the fp32 ref oracle
    on a batch with padded atoms (21 atoms -> ragged 8-row tile) and ~10%
    masked edges."""
    E, n_atoms, k = 64, 21, 4
    args = _interaction_inputs(jax.random.PRNGKey(2), E, n_atoms, k)
    blocking, _ = _blocking_arrays(args[4], args[5], n_atoms)
    want = _interaction_grads(ISPEC, "ref", None, args)
    got = _interaction_grads(ISPEC, impl, blocking, args)
    _assert_tree_close(got, want, precision)
    _assert_tree_differs(got, want)


@pytest.mark.parametrize("precision,impl", VARIANTS)
def test_interaction_precision_empty_bin_exact_zeros(precision, impl):
    """Reduced precision must not leak noise into an all-masked bin: zero
    is exactly representable at every precision, so cotangents are exact
    zeros — not merely small."""
    args = _interaction_inputs(jax.random.PRNGKey(3), 32, 9, 4, edge_keep=0.0)
    blocking, _ = _blocking_arrays(args[4], args[5], 9)
    for g in _interaction_grads(ISPEC, impl, blocking, args):
        np.testing.assert_array_equal(np.asarray(g), np.zeros_like(g))


@pytest.mark.slow
@pytest.mark.parametrize("precision,impl", VARIANTS)
def test_interaction_precision_hub_spill(precision, impl):
    """Hub receiver spilling across virtual tiles: the reduced-precision
    backward's tile-row gather keeps grad parity within the contract."""
    E, n_atoms, k = 64, 16, 4
    Y, h, R, senders, _, _ = _interaction_inputs(
        jax.random.PRNGKey(4), E, n_atoms, k
    )
    receivers = jnp.concatenate(
        [jnp.full((48,), 3, jnp.int32), jnp.full((16,), 11, jnp.int32)]
    )
    edge_mask = jnp.ones((E,), bool)
    args = (Y, h, R, senders, receivers, edge_mask)
    blocking, b = _blocking_arrays(receivers, edge_mask, n_atoms)
    assert (np.asarray(b.tile_base) == 0).sum() == 3  # real hub spill
    _assert_tree_close(
        _interaction_grads(ISPEC, impl, blocking, args),
        _interaction_grads(ISPEC, "ref", None, args),
        precision,
    )


@pytest.mark.parametrize("precision,impl", VARIANTS)
def test_interaction_second_order_stays_fp32(precision, impl):
    """The interaction backward's own derivative rule runs its kernels at
    fp32 whatever the op's precision: grad-of-grad through the reduced-
    precision op, taken in the first-order cotangent and the operands, is
    the fp32 op's bit for bit."""
    args = _interaction_inputs(jax.random.PRNGKey(5), 48, 21, 4)
    blocking, _ = _blocking_arrays(args[4], args[5], 21)
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(6), 4)
    g = jax.random.normal(k1, (21, 4, ISPEC.tp.out_spec.dim), jnp.float32)
    cts = [jax.random.normal(k, x.shape, jnp.float32)
           for k, x in zip((k2, k3, k4), args[:3])]

    def second_order(name):
        fn = registry.resolve("interaction", name, ISPEC)

        def inner(gg, y, hh, r):
            _, vjp = jax.vjp(
                lambda y, hh, r: fn(y, hh, r, *args[3:], blocking=blocking),
                y, hh, r,
            )
            return sum(jnp.sum(c * d) for c, d in zip(cts, vjp(gg)))

        return jax.jit(jax.grad(inner, argnums=(0, 1, 2, 3)))(g, *args[:3])

    for got, want in zip(second_order(impl), second_order("pallas")):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# config plumbing: MaceConfig.precision -> variant impl names
# ---------------------------------------------------------------------------

TINY_KW = dict(n_species=10, channels=4, hidden_ls=(0, 1), sh_lmax=2,
               a_ls=(0, 1, 2), correlation=2, n_interactions=2,
               avg_num_neighbors=8.0)


def test_mace_config_precision_resolution():
    cfg = MaceConfig(**TINY_KW, impl="pallas", precision="bf16")
    assert cfg.symcon_impl_name == "pallas_bf16"
    assert cfg.interaction_impl_name == "pallas_bf16"
    assert cfg.interaction_spec_at(0).precision == "bf16"
    # already-suffixed names pass through (autotune resolves to variants)
    cfg2 = dataclasses.replace(cfg, impl="pallas_bf16")
    assert cfg2.symcon_impl_name == "pallas_bf16"
    # fp32 leaves every name untouched
    cfg3 = MaceConfig(**TINY_KW, impl="fused")
    assert cfg3.symcon_impl_name == "fused"
    assert cfg3.interaction_spec_at(0).precision == "fp32"
    # "auto" defers to the autotuner (which keys on precision itself)
    cfg4 = MaceConfig(**TINY_KW, impl="auto", precision="bf16")
    assert cfg4.symcon_impl_name == "auto"
    # non-pallas impls have no reduced-precision variant: loud failure,
    # never a silent fp32 run
    cfg5 = MaceConfig(**TINY_KW, impl="fused", precision="bf16")
    with pytest.raises(ValueError, match="no 'bf16' variant"):
        cfg5.symcon_impl_name
    with pytest.raises(ValueError):
        MaceConfig(**TINY_KW, precision="fp16")


# ---------------------------------------------------------------------------
# engine matrix: bf16 loss trajectory vs the fp32 sequential oracle
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_engine_bf16_loss_trajectory_drift():
    """End-to-end training drift pin: a bf16 run (pallas kernels, interpret
    mode) tracks the fp32 sequential oracle within the bf16 contract while
    actually diverging from it (the knob reaches the engine)."""
    from repro.data.molecules import SyntheticCFMDataset
    from repro.train.train_loop import Trainer, TrainerConfig

    ds = SyntheticCFMDataset(12, seed=0, max_atoms=24)
    kw = dict(capacity=32, edge_factor=32, max_graphs=4, lr=2e-3,
              n_ranks=1, engine="sequential", prefetch=0, ckpt_dir=None)
    mcfg = MaceConfig(**TINY_KW, impl="pallas")
    steps = 3

    tr32 = Trainer(mcfg, TrainerConfig(**kw), ds, seed=0)
    o32 = tr32.train(n_epochs=1, max_steps=steps)
    tr16 = Trainer(mcfg, TrainerConfig(precision="bf16", **kw), ds, seed=0)
    assert tr16.mace_cfg.precision == "bf16"
    assert tr16.mace_cfg.symcon_impl_name == "pallas_bf16"
    o16 = tr16.train(n_epochs=1, max_steps=steps)

    l32 = np.asarray([h["loss"] for h in o32["history"]])
    l16 = np.asarray([h["loss"] for h in o16["history"]])
    assert np.all(np.isfinite(l16))
    drift = np.abs(l16 - l32) / np.maximum(np.abs(l32), 1e-12)
    assert drift.max() <= PRECISION_TOL["bf16"], drift
    assert drift.max() > 0.0  # bitwise-equal curves mean a dead knob
