"""Backward-pass kernel validation: the hand-written Pallas VJPs (interpret
mode on CPU) against the ref impls' autodiff — ``jax.grad`` parity for the
symmetric contraction (dA + dW through the species gather) and the fused
interaction (dY/dh/dR through blocked gather + TP-transpose), under padded
atoms, masked edges, empty bins, and hub-spill blockings; grad-of-grad
parity for the interaction backward's own second-order rule (the same
kernels at swapped operands); a hypothesis property over random specs; the
registry's backward capability metadata and the missing-backward
differentiation guard; and a slow-marked bwd speed-regression guard
mirroring the forward one.
"""
import dataclasses
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests.hypothesis_support import given, settings, st

from repro.core.channelwise_tp import TPSpec
from repro.core.interaction import InteractionSpec
from repro.core.irreps import LSpec, lspec, sh_spec
from repro.core.symmetric_contraction import SymConSpec, init_symcon_weights
from repro.data.blocking import block_edges
from repro.kernels import registry
from repro.kernels.channelwise_tp.ops import interaction_pallas_op, tp_pallas
from repro.kernels.channelwise_tp.ref import interaction_reference, tp_reference
from repro.kernels.symmetric_contraction.ops import symcon_pallas
from repro.kernels.symmetric_contraction.ref import symcon_reference


# ---------------------------------------------------------------------------
# symmetric contraction backward
# ---------------------------------------------------------------------------


def _symcon_grads(fn, A, species, W):
    """d(sum fn^2)/d(A, W) — W is the per-(L,nu) weight dict, so the pallas
    path exercises dW through the species gather's own VJP too."""
    loss = lambda a, w: jnp.sum(fn(a, species, w) ** 2)
    return jax.grad(loss, argnums=(0, 1))(A, W)


def _assert_tree_allclose(got, want, rtol=2e-4, atol=2e-4):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=rtol, atol=atol
        )


# nu=3 builds the cubic tables (minutes): slow sweep; N=33 exercises the
# ragged final atom tile (padding rows must contribute zero cotangent); the
# nu=1 (no-partial-product product rule) case joins the slow sweep to keep
# the quick tier inside its time contract
@pytest.mark.parametrize(
    "nu,N", [pytest.param(1, 16, marks=pytest.mark.slow), (2, 33),
             pytest.param(3, 16, marks=pytest.mark.slow)]
)
def test_symcon_bwd_kernel_grad_parity(nu, N):
    spec = SymConSpec(lspec(0, 1, 2), lspec(0, 1), nu)
    key = jax.random.PRNGKey(nu * 10 + N)
    k1, k2, k3 = jax.random.split(key, 3)
    k_ch = 4
    A = jax.random.normal(k1, (N, k_ch, spec.in_spec.dim), jnp.float32)
    species = jax.random.randint(k2, (N,), 0, 3)
    W = init_symcon_weights(k3, spec, 3, k_ch)

    want = _symcon_grads(
        lambda a, s, w: symcon_reference(a, s, w, spec), A, species, W
    )
    got = _symcon_grads(
        lambda a, s, w: symcon_pallas(a, s, w, spec, block_n=8, interpret=True),
        A, species, W,
    )
    _assert_tree_allclose(got, want)


def test_symcon_bwd_under_jit_and_registry():
    """The custom_vjp must survive jit and the registry-resolved binding
    (the path the engine's value_and_grad actually takes)."""
    spec = SymConSpec(lspec(0, 1), lspec(0, 1), 2)
    key = jax.random.PRNGKey(3)
    A = jax.random.normal(key, (16, 4, spec.in_spec.dim), jnp.float32)
    species = jnp.zeros((16,), jnp.int32)
    W = init_symcon_weights(key, spec, 1, 4)
    fn = registry.resolve("symcon", "pallas", spec)
    ref = registry.resolve("symcon", "ref", spec)
    grad = jax.jit(jax.grad(lambda a, w: jnp.sum(fn(a, species, w) ** 2),
                            argnums=(0, 1)))
    want = jax.grad(lambda a, w: jnp.sum(ref(a, species, w) ** 2),
                    argnums=(0, 1))(A, W)
    _assert_tree_allclose(grad(A, W), want)


# ---------------------------------------------------------------------------
# channelwise TP backward (identity-blocked TP-transpose kernel)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_tp_bwd_kernel_grad_parity():
    spec = TPSpec(sh_spec(2), lspec(0, 1), lspec(0, 1, 2))
    key = jax.random.PRNGKey(4)
    k1, k2, k3 = jax.random.split(key, 3)
    E, k = 20, 4  # E=20 with block_e=16: ragged padded tail block
    Y = jax.random.normal(k1, (E, spec.y_spec.dim), jnp.float32)
    h = jax.random.normal(k2, (E, k, spec.h_spec.dim), jnp.float32)
    R = jax.random.normal(k3, (E, spec.n_paths, k), jnp.float32)

    def grads(fn):
        return jax.grad(
            lambda y, hh, r: jnp.sum(fn(y, hh, r) ** 2), argnums=(0, 1, 2)
        )(Y, h, R)

    want = grads(lambda y, hh, r: tp_reference(y, hh, r, spec))
    got = grads(
        lambda y, hh, r: tp_pallas(y, hh, r, spec, block_e=16, interpret=True)
    )
    _assert_tree_allclose(got, want)


# ---------------------------------------------------------------------------
# interaction backward (blocked gather + TP-transpose)
# ---------------------------------------------------------------------------

ISPEC = InteractionSpec(
    TPSpec(sh_spec(2), lspec(0, 1), lspec(0, 1, 2)),
    avg_num_neighbors=4.0,
    block_n=8,
)


def _interaction_inputs(key, E, n_atoms, k, edge_keep=0.9):
    k1, k2, k3, k4, k5, k6 = jax.random.split(key, 6)
    Y = jax.random.normal(k1, (E, ISPEC.tp.y_spec.dim), jnp.float32)
    h = jax.random.normal(k2, (n_atoms, k, ISPEC.tp.h_spec.dim), jnp.float32)
    R = jax.random.normal(k3, (E, ISPEC.tp.n_paths, k), jnp.float32)
    senders = jax.random.randint(k4, (E,), 0, n_atoms)
    receivers = jax.random.randint(k5, (E,), 0, n_atoms)
    edge_mask = jax.random.bernoulli(k6, edge_keep, (E,))
    return Y, h, R, senders, receivers, edge_mask


def _blocking_arrays(receivers, edge_mask, n_atoms, block_n=8, block_e=16):
    b = block_edges(
        np.asarray(receivers), np.asarray(edge_mask), n_atoms,
        block_n=block_n, block_e=block_e,
    )
    return {
        "perm": jnp.asarray(b.perm, jnp.int32),
        "valid": jnp.asarray(b.valid),
        "local": jnp.asarray(b.local_rcv),
        "base": jnp.asarray(b.tile_base),
    }, b


def _interaction_grads(spec, blocking, args, interpret=True):
    Y, h, R, senders, receivers, edge_mask = args

    def loss(y, hh, r):
        return jnp.sum(
            interaction_pallas_op(
                y, hh, r, senders, receivers, edge_mask,
                spec=spec, blocking=blocking, interpret=interpret,
            ) ** 2
        )

    return jax.grad(loss, argnums=(0, 1, 2))(Y, h, R)


def _ref_grads(args):
    Y, h, R, senders, receivers, edge_mask = args

    def loss(y, hh, r):
        return jnp.sum(
            interaction_reference(
                y, hh, r, senders, receivers, edge_mask, ISPEC
            ) ** 2
        )

    return jax.grad(loss, argnums=(0, 1, 2))(Y, h, R)


def test_interaction_bwd_grad_parity_masked_padded():
    """The acceptance core (quick tier): the dedicated blocked backward on
    a batch with padded atoms (21: ragged last tile) and masked edges."""
    args = _interaction_inputs(jax.random.PRNGKey(5), 48, 21, 4)
    blocking, _ = _blocking_arrays(args[4], args[5], 21)
    _assert_tree_allclose(
        _interaction_grads(ISPEC, blocking, args), _ref_grads(args)
    )


@pytest.mark.slow
@pytest.mark.parametrize("bwd_impl", ["pallas", "xla"])
def test_interaction_bwd_grad_parity_full_matrix(bwd_impl):
    """Both backward impls on both paths (blocked + capability fallback)."""
    args = _interaction_inputs(jax.random.PRNGKey(5), 48, 21, 4)
    blocking, _ = _blocking_arrays(args[4], args[5], 21)
    spec = dataclasses.replace(ISPEC, bwd_impl=bwd_impl)
    want = _ref_grads(args)
    _assert_tree_allclose(_interaction_grads(spec, blocking, args), want)
    _assert_tree_allclose(_interaction_grads(spec, None, args), want)


@pytest.mark.slow
def test_interaction_bwd_empty_bin_grads_are_zero():
    """Every edge masked: cotangents must be exact zeros (masked slots in
    the blocked layout gate the gather, so nothing leaks from the padding
    rows that alias edge 0)."""
    args = _interaction_inputs(jax.random.PRNGKey(6), 32, 9, 4, edge_keep=0.0)
    blocking, _ = _blocking_arrays(args[4], args[5], 9)
    got = _interaction_grads(ISPEC, blocking, args)
    for g in got:
        np.testing.assert_array_equal(np.asarray(g), np.zeros_like(g))


@pytest.mark.slow
def test_interaction_bwd_hub_spill_blocking():
    """A hub receiver whose degree exceeds the tile edge budget spills into
    extra virtual tiles sharing one base; the backward's tile-row gather
    must hand every spill tile the same cotangent row."""
    args = _hub_spill_inputs()
    blocking, b = _blocking_arrays(args[4], args[5], 16)
    assert (np.asarray(b.tile_base) == 0).sum() == 3  # real hub spill
    _assert_tree_allclose(
        _interaction_grads(ISPEC, blocking, args), _ref_grads(args)
    )


def _hub_spill_inputs():
    """A hub receiver (atom 3, 48 edges) whose degree exceeds the tile edge
    budget, so it spills into extra virtual tiles sharing one base."""
    E, n_atoms, k = 64, 16, 4
    Y, h, R, senders, _, _ = _interaction_inputs(
        jax.random.PRNGKey(7), E, n_atoms, k
    )
    receivers = jnp.concatenate(
        [jnp.full((48,), 3, jnp.int32), jnp.full((16,), 11, jnp.int32)]
    )
    return Y, h, R, senders, receivers, jnp.ones((E,), bool)


def _second_order_grads(op, args, g, cts):
    """Grad-of-grad through ``op``'s backward: the gradient in
    ``(g, Y, h, R)`` of ``<(a, b, c), vjp_op(g)>``, with cotangents
    ``(a, b, c)`` on ``(dY, dh, dR)``."""
    Y, h, R, senders, receivers, edge_mask = args

    def second(gg, y, hh, r):
        _, vjp = jax.vjp(
            lambda y, hh, r: op(y, hh, r, senders, receivers, edge_mask),
            y, hh, r,
        )
        return sum(jnp.sum(c * d) for c, d in zip(cts, vjp(gg)))

    return jax.jit(jax.grad(second, argnums=(0, 1, 2, 3)))(g, Y, h, R)


@pytest.mark.parametrize("case", [
    "masked_padded",
    pytest.param("hub_spill", marks=pytest.mark.slow),
    pytest.param("empty_bin", marks=pytest.mark.slow),
    pytest.param("unblocked", marks=pytest.mark.slow),
])
def test_interaction_second_order_grad_parity(case):
    """The backward op's own derivative rule (three forward and three
    backward kernel calls) against grad-of-grad through the ref impl, with
    the cotangents on all of ``dY``, ``dh``, ``dR`` nonzero: on padded atoms
    and masked edges, a hub-spill blocking, an empty bin (exact zeros), and
    the unblocked capability fallback."""
    if case == "hub_spill":
        args, n_atoms = _hub_spill_inputs(), 16
    elif case == "empty_bin":
        args, n_atoms = _interaction_inputs(
            jax.random.PRNGKey(6), 32, 9, 4, edge_keep=0.0
        ), 9
    else:
        args, n_atoms = _interaction_inputs(jax.random.PRNGKey(5), 48, 21, 4), 21
    blocking, b = _blocking_arrays(args[4], args[5], n_atoms)
    if case == "hub_spill":
        assert (np.asarray(b.tile_base) == 0).sum() == 3  # real hub spill
    if case == "unblocked":
        blocking = None
    Y, h, R = args[:3]
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(8), 4)
    g = jax.random.normal(k1, (n_atoms,) + h.shape[1:-1]
                          + (ISPEC.tp.out_spec.dim,), jnp.float32)
    cts = [jax.random.normal(k, x.shape, jnp.float32)
           for k, x in zip((k2, k3, k4), (Y, h, R))]

    got = _second_order_grads(
        lambda *a: interaction_pallas_op(
            *a, spec=ISPEC, blocking=blocking, interpret=True
        ),
        args, g, cts,
    )
    want = _second_order_grads(
        lambda *a: interaction_reference(*a, ISPEC), args, g, cts
    )
    _assert_tree_allclose(got, want)
    if case == "empty_bin":
        for x in got:
            np.testing.assert_array_equal(np.asarray(x), np.zeros_like(x))


@pytest.mark.slow
@given(data=st.data())
@settings(max_examples=10, deadline=None)
def test_interaction_bwd_grad_parity_property(data):
    """Hypothesis sweep over random specs/shapes/blocking geometries: the
    dedicated backward matches the ref VJP oracle."""
    h_ls = data.draw(st.sampled_from([(0,), (0, 1)]))
    out_ls = data.draw(st.sampled_from([(0, 1), (0, 1, 2)]))
    sh_l = data.draw(st.sampled_from([1, 2]))
    spec = InteractionSpec(
        TPSpec(sh_spec(sh_l), LSpec(h_ls), LSpec(out_ls)),
        avg_num_neighbors=float(data.draw(st.sampled_from([1.0, 4.0]))),
        block_n=data.draw(st.sampled_from([4, 8])),
    )
    E = data.draw(st.integers(1, 40))
    n_atoms = data.draw(st.integers(1, 24))
    k = data.draw(st.sampled_from([1, 4]))
    seed = data.draw(st.integers(0, 2**31 - 1))
    key = jax.random.PRNGKey(seed)
    k1, k2, k3, k4, k5, k6 = jax.random.split(key, 6)
    Y = jax.random.normal(k1, (E, spec.tp.y_spec.dim), jnp.float32)
    h = jax.random.normal(k2, (n_atoms, k, spec.tp.h_spec.dim), jnp.float32)
    R = jax.random.normal(k3, (E, spec.tp.n_paths, k), jnp.float32)
    senders = jax.random.randint(k4, (E,), 0, n_atoms)
    receivers = jax.random.randint(k5, (E,), 0, n_atoms)
    edge_mask = jax.random.bernoulli(k6, 0.8, (E,))
    args = (Y, h, R, senders, receivers, edge_mask)
    blocking, _ = _blocking_arrays(
        receivers, edge_mask, n_atoms, block_n=spec.block_n, block_e=8
    )

    def loss(fn):
        return lambda y, hh, r: jnp.sum(
            fn(y, hh, r, senders, receivers, edge_mask) ** 2
        )

    want = jax.grad(
        loss(lambda *a: interaction_reference(*a, spec)), argnums=(0, 1, 2)
    )(Y, h, R)
    got = jax.grad(
        loss(lambda *a: interaction_pallas_op(
            *a, spec=spec, blocking=blocking, interpret=True
        )),
        argnums=(0, 1, 2),
    )(Y, h, R)
    _assert_tree_allclose(got, want, rtol=5e-4, atol=5e-4)


# ---------------------------------------------------------------------------
# registry backward-capability metadata + differentiation guard
# ---------------------------------------------------------------------------


def test_registry_reports_has_custom_bwd():
    for kind in ("symcon", "channelwise_tp", "interaction"):
        caps = registry.capabilities(kind)
        assert caps["pallas"]["has_custom_bwd"], kind
        assert not caps["ref"]["has_custom_bwd"], kind
        assert "pallas" in registry.available(kind, with_custom_bwd=True)
        assert "ref" not in registry.available(kind, with_custom_bwd=True)
        assert "ref" in registry.available(kind, with_custom_bwd=False)
    # single-impl view + unknown name
    one = registry.capabilities("symcon", "pallas")
    assert set(one) == {"pallas"} and one["pallas"]["uses_pallas"]
    with pytest.raises(KeyError):
        registry.capabilities("symcon", "no_such_impl")


def test_resolve_guards_differentiating_compiled_pallas_without_bwd():
    """A compiled-pallas impl without a custom VJP must fail *loudly* when
    differentiated (clear error naming the impl), while its forward stays
    usable.  Registered on the current platform so the guard engages."""
    platform = jax.default_backend()

    @registry.register(
        "symcon", "guard_test_impl", platforms=(platform,),
        uses_pallas=True, has_custom_bwd=False,
    )
    def _build(spec):
        return lambda A, species, W: A * 2.0

    try:
        spec = SymConSpec(lspec(0, 1), lspec(0, 1), 2)
        fn = registry.resolve("symcon", "guard_test_impl", spec)
        A = jnp.ones((4, 2, spec.in_spec.dim))
        # forward-only use is untouched
        np.testing.assert_allclose(np.asarray(fn(A, None, None)), 2.0)
        with pytest.raises(NotImplementedError, match="guard_test_impl"):
            jax.grad(lambda a: jnp.sum(fn(a, None, None)))(A)
    finally:
        registry.unregister("symcon", "guard_test_impl")


def test_resolve_leaves_interpret_only_bindings_differentiable():
    """On CPU the built-in pallas impls are interpret-only (platform not in
    ``platforms``), so resolve() must NOT wrap them even when
    has_custom_bwd is False for a registered third-party impl."""
    if jax.default_backend() != "cpu":
        pytest.skip("interpret-only semantics are the CPU case")

    @registry.register(
        "symcon", "interpret_only_impl", platforms=("tpu",),
        interpret_only_on=("cpu",), uses_pallas=True, has_custom_bwd=False,
    )
    def _build(spec):
        return lambda A, species, W: A * 3.0

    try:
        spec = SymConSpec(lspec(0, 1), lspec(0, 1), 2)
        fn = registry.resolve("symcon", "interpret_only_impl", spec)
        A = jnp.ones((4, 2, spec.in_spec.dim))
        g = jax.grad(lambda a: jnp.sum(fn(a, None, None)))(A)
        np.testing.assert_allclose(np.asarray(g), 3.0)
    finally:
        registry.unregister("symcon", "interpret_only_impl")


def test_interaction_spec_rejects_unknown_bwd_impl():
    with pytest.raises(ValueError):
        dataclasses.replace(ISPEC, bwd_impl="triton")


# ---------------------------------------------------------------------------
# speed-regression guard (mirrors the forward blocking guard): the backward
# must stay within a small constant factor of the forward
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_bwd_speed_regression_guard():
    """Compiled (XLA path) fwd+bwd through the fused interaction must stay
    within a small constant factor of fwd alone — backward is ~2x the
    forward FLOPs, so a blow-up here means a backward-path regression
    (e.g. an accidental dense re-materialization in a VJP)."""
    spec = InteractionSpec(
        TPSpec(sh_spec(3), lspec(0, 1), lspec(0, 1, 2, 3)),
        avg_num_neighbors=12.0,
    )
    E, N, k = 4096, 512, 16
    key = jax.random.PRNGKey(0)
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    Y = jax.random.normal(k1, (E, spec.tp.y_spec.dim))
    h = jax.random.normal(k2, (N, k, spec.tp.h_spec.dim))
    R = jax.random.normal(k3, (E, spec.tp.n_paths, k))
    senders = jax.random.randint(k4, (E,), 0, N)
    receivers = jax.random.randint(k5, (E,), 0, N)
    edge_mask = jnp.ones((E,), bool)
    fn = registry.resolve("interaction", "fused", spec)

    fwd = jax.jit(lambda y, hh, r: jnp.sum(
        fn(y, hh, r, senders, receivers, edge_mask) ** 2))
    vg = jax.jit(jax.value_and_grad(
        lambda y, hh, r: jnp.sum(
            fn(y, hh, r, senders, receivers, edge_mask) ** 2),
        argnums=(0, 1, 2)))

    def t(f):
        jax.block_until_ready(f(Y, h, R))  # compile + warm
        t0 = time.perf_counter()
        for _ in range(3):
            jax.block_until_ready(f(Y, h, R))
        return (time.perf_counter() - t0) / 3

    t_fwd, t_both = t(fwd), t(vg)
    assert t_both < 10 * t_fwd + 0.05, (
        f"fwd+bwd {t_both:.4f}s vs fwd {t_fwd:.4f}s: backward regression"
    )


# ---------------------------------------------------------------------------
# bench --grad artifact (the acceptance row contract)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_bench_kernels_grad_writes_trajectory_json(tmp_path):
    """``bench_kernels --grad --quick`` runs green and the JSON artifact
    holds fwd AND fwd_bwd rows for all three kernel kinds (pallas rows
    included: the hand-written backward kernels are what's timed) — and
    re-running *appends* a run to the trajectory instead of overwriting."""
    import json as _json

    from benchmarks.bench_kernels import bench_matrix, write_bench_json

    rows = bench_matrix(grad=True, quick=True, repeats=1)
    path = tmp_path / "BENCH_kernels.json"
    payload = write_bench_json(rows, path, grad=True, quick=True)
    on_disk = _json.loads(path.read_text())
    assert on_disk["schema"] == payload["schema"] == 1
    assert len(on_disk["runs"]) == 1
    run = on_disk["runs"][0]
    got = {(r["kind"], r["impl"], r["mode"]) for r in run["rows"]}
    for kind in ("symcon", "channelwise_tp", "interaction"):
        for impl in ("ref", "fused", "pallas"):
            assert (kind, impl, "fwd") in got
            assert (kind, impl, "fwd_bwd") in got
    assert all(r["seconds"] > 0 for r in run["rows"])
    # the trajectory accumulates across runs
    write_bench_json(rows, path, grad=True, quick=True)
    assert len(_json.loads(path.read_text())["runs"]) == 2
