"""Public wrappers for the fused channelwise-TP(+scatter) kernel.

Batch contract (the model/pipeline handshake)
---------------------------------------------
Edge blocking is a *data-pipeline product*: ``data.blocking.block_edges``
runs on the host next to Algorithm-1 collation and its arrays ride inside
the batch dict under ``blk_*`` keys (``data.blocking.BLOCKING_BATCH_KEYS``),
shape-stable per ``BinShape`` and stacked to ``[R, ...]`` for shard_map.
``core/mace.py`` extracts them (``blocking_from_batch``) and hands them —
untouched — to the ``interaction`` impl resolved from ``kernels.registry``:

``interaction_pallas_op``
    The registered ``interaction/pallas`` impl.  With blocking it runs the
    fully fused TP+scatter kernel (sort + one-hot MXU matmul; the TPU-native
    ``atomicAdd`` — see kernel.py) over the pre-blocked edges, then a cheap
    ``[T*block_n] -> [N]`` segment-add folds the virtual tiles back onto
    atom rows.  Without blocking it *falls back* (capability check) to the
    TP-only kernel + XLA segment-sum, so the impl stays selectable on
    batches that carry no blocking metadata.

``tp_pallas``
    TP-only drop-in for ``tp_fused`` (scatter outside); used by the
    fallback above and by ``MaceConfig(impl="pallas")``'s contraction stage.

Differentiation contract (``InteractionSpec.bwd_impl``)
-------------------------------------------------------
Every op here differentiates through a ``jax.custom_vjp``; since backward is
~2/3 of training FLOPs, the default backward is a *dedicated Pallas kernel*,
not the forward's autodiff trace:

``bwd_impl="pallas"`` (default)
    The scatter-transpose is a *gather* over the same pre-blocked edge tiles
    (``kernel.tp_bwd_pallas_raw``): each edge slot reads its receiver's
    cotangent row via the transpose of the forward's one-hot MXU matmul,
    then the TP-transpose produces ``dY/dh/dR`` per edge slot in VREGs.  A
    plain XLA scatter-add un-permutes slots back to edge order (valid slots
    are a permutation; masked slots carry exact zeros) and a segment-sum
    over senders folds ``dh`` onto atoms — the exact adjoints of the
    forward's host-side blocking gathers.

``bwd_impl="xla"``
    The previous behaviour, retained for capability-gated platforms: the
    VJP of the numerically-equivalent ``interaction_fused`` formulation,
    which XLA differentiates to any order.

Saved-residual memory contract: the custom_vjp stores exactly the op's own
inputs — ``(Y, h_node, R)`` plus the integer/bool operands and blocking
arrays (float0 cotangents) — never the ``[E, k, d_out]`` message tensor or
any blocked copy; the backward re-gathers its blocked operands from these
residuals just like the forward does.

Second-order autodiff: ``pallas_call`` has no JVP rule, and every training
step is a grad-of-grad (forces inside the loss), so each backward kernel is
*itself* wrapped in a ``custom_vjp``.  For the interaction ops its rule is
exact and runs on the same two kernels: the op is trilinear in
``(Y, h, R)``, so the derivative of its backward is three forward and three
backward kernel calls at swapped operands (``_second_order``), with the
layout gathers and scatters done once.  These calls carry the first-order
kernels' names, so a profile prices them per call alike.  A third
derivative is not defined.  ``tp_pallas``'s backward keeps ``jax.vjp`` of
its XLA twin ``tp_fused`` as its derivative rule.

Mixed precision: ``tp_pallas`` takes an explicit ``precision`` knob; the
interaction ops read ``InteractionSpec.precision`` (the spec is already a
nondiff static everywhere, so no custom_vjp signature changes).  Both route
the knob to the kernels' operand-load rounding (fp32 accumulation — see
``repro.kernels.precision``); every second-order pass stays fp32 (the
interaction's kernel calls there run at ``"fp32"``, ``tp_fused`` is fp32).
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.channelwise_tp import TPSpec, TPTables, build_tp_tables, tp_fused
from repro.core.interaction import (
    InteractionSpec,
    aggregate_edge_messages,
    interaction_fused,
)
# Re-exported for backward compatibility: blocking is built by the data
# pipeline now, but kernel-side callers/tests import it from here too.
from repro.data.blocking import EdgeBlocking, block_edges  # noqa: F401

from repro.kernels.precision import check_precision

from .kernel import tp_bwd_pallas_raw, tp_scatter_pallas_raw


def _identity_blocking(E_p: int, block_e: int, dtype):
    """One "atom" tile per edge block; local receiver = position in block."""
    n_tiles = E_p // block_e
    lr = jnp.tile(jnp.arange(block_e, dtype=jnp.int32), n_tiles)[:, None]
    em = jnp.ones((E_p, 1), dtype)
    return n_tiles, lr, em


def _block_edge_operands(Y, h_send, R, block_e):
    """Pad + k-minor-transpose per-edge operands to kernel layout."""
    E = h_send.shape[0]
    pad = (-E) % block_e
    Y_b = jnp.pad(Y, ((0, pad), (0, 0)))
    h_b = jnp.pad(jnp.swapaxes(h_send, 1, 2), ((0, pad), (0, 0), (0, 0)))
    R_b = jnp.pad(R, ((0, pad), (0, 0), (0, 0)))  # [E_p, n_paths, k] (k-minor)
    return Y_b, h_b, R_b, E + pad


@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _tp_op(spec: TPSpec, block_e: int, interpret: bool, precision: str,
           Y, h_send, R):
    """TP-only core op (identity 'scatter': each edge is its own segment)."""
    Y_b, h_b, R_b, E_p = _block_edge_operands(Y, h_send, R, block_e)
    n_tiles, lr, em = _identity_blocking(E_p, block_e, h_b.dtype)
    A_t = tp_scatter_pallas_raw(
        Y_b, h_b, R_b, lr, em, spec, build_tp_tables(spec),
        n_atom_tiles=n_tiles, block_n=block_e, block_e=block_e,
        interpret=interpret, precision=precision,
    )                                             # [E_p, d_out, k]
    return jnp.swapaxes(A_t, 1, 2)[: h_send.shape[0]]


def _tp_op_fwd(spec, block_e, interpret, precision, Y, h_send, R):
    return _tp_op(spec, block_e, interpret, precision, Y, h_send, R), (
        Y, h_send, R,
    )


@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _tp_bwd_op(spec, block_e, interpret, precision, g, Y, h_send, R):
    """First-order TP backward as a closed op: the identity-blocked
    TP-transpose kernel, shielded from linearization by its own custom_vjp
    (see the module docstring's second-order note)."""
    E = h_send.shape[0]
    Y_b, h_b, R_b, E_p = _block_edge_operands(Y, h_send, R, block_e)
    n_tiles, lr, em = _identity_blocking(E_p, block_e, h_b.dtype)
    G_t = jnp.pad(jnp.swapaxes(g, 1, 2), ((0, E_p - E), (0, 0), (0, 0)))
    dY_b, dh_b, dR_b = tp_bwd_pallas_raw(
        G_t, Y_b, h_b, R_b, lr, em, spec, build_tp_tables(spec),
        n_atom_tiles=n_tiles, block_n=block_e, block_e=block_e,
        interpret=interpret, precision=precision,
    )
    return dY_b[:E], jnp.swapaxes(dh_b[:E], 1, 2), dR_b[:E]


def _tp_bwd_op_fwd(spec, block_e, interpret, precision, g, Y, h_send, R):
    return _tp_bwd_op(spec, block_e, interpret, precision, g, Y, h_send, R), (
        g, Y, h_send, R,
    )


def _tp_bwd_op_bwd(spec, block_e, interpret, precision, res, ct):
    g, Y, h_send, R = res
    tables = build_tp_tables(spec)

    def bwd_xla(gg, y, h, r):
        _, vjp = jax.vjp(
            lambda yy, hh, rr: tp_fused(yy, hh, rr, spec, tables), y, h, r
        )
        return vjp(gg)

    _, vjp2 = jax.vjp(bwd_xla, g, Y, h_send, R)
    return vjp2(tuple(ct))


_tp_bwd_op.defvjp(_tp_bwd_op_fwd, _tp_bwd_op_bwd)


def _tp_op_bwd(spec, block_e, interpret, precision, res, g):
    Y, h_send, R = res
    return _tp_bwd_op(spec, block_e, interpret, precision, g, Y, h_send, R)


_tp_op.defvjp(_tp_op_fwd, _tp_op_bwd)


def tp_pallas(
    Y: jnp.ndarray,
    h_send: jnp.ndarray,
    R: jnp.ndarray,
    spec: TPSpec,
    tables: TPTables | None = None,
    *,
    block_e: int = 128,
    interpret: bool | None = None,
    precision: str = "fp32",
) -> jnp.ndarray:
    """TP-only drop-in for ``tp_fused``; forward *and* backward are Pallas
    kernels (the backward via the identity-blocked ``tp_bwd_pallas_raw``).
    The fully fused variant is ``interaction_pallas_op``."""
    # the custom_vjp core always binds the canonical lru-cached tables (it
    # cannot close over an unhashable argument), so a caller-supplied
    # substitute would be silently ignored — reject anything non-canonical
    if tables is not None and tables is not build_tp_tables(spec):
        raise ValueError(
            "tp_pallas cannot bind non-canonical TPTables; pass tables=None "
            "(build_tp_tables(spec) is lru-cached and used internally)"
        )
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    return _tp_op(spec, block_e, bool(interpret), check_precision(precision),
                  Y, h_send, R)


# ---------------------------------------------------------------------------
# fused interaction (TP + scatter) over pre-blocked edges
# ---------------------------------------------------------------------------


def _tile_rows(base: jnp.ndarray, block_n: int) -> jnp.ndarray:
    """[T*block_n] atom row per tile row (bases repeat for hub/overflow
    tiles; padding tiles point at the trash rows >= n_atoms)."""
    return (
        base[:, None] + jnp.arange(block_n, dtype=base.dtype)
    ).reshape(-1)


class _Layout(NamedTuple):
    """How one interaction op lays its operands out for the two kernels.

    ``slots`` gathers ``(Y, h_node, R)``-shaped arrays into the kernels'
    edge-slot layout and ``unslot`` is its adjoint (scatter-add back to
    edges, segment-sum over senders to atoms); ``fold`` maps the forward
    kernel's tile output to ``A [N, k, d_out]`` and ``unfold`` is its
    adjoint.  ``forward``/``backward`` are the two kernels at a given
    precision.  Every map is linear, so the op and both derivative orders
    are compositions of these six."""

    slots: Callable
    unslot: Callable
    fold: Callable
    unfold: Callable
    forward: Callable
    backward: Callable


def _blocked_layout(spec, interpret, n_atoms, senders, receivers, edge_mask,
                    perm, valid, local, base):
    """Fused TP+scatter over the data pipeline's pre-blocked edges.

    ``receivers``/``edge_mask`` are unused (the blocking arrays encode both)
    but kept in the uniform op signature."""
    del receivers, edge_mask
    T = base.shape[0]
    send_b = senders[perm]
    rows = _tile_rows(base, spec.block_n)
    kernel_args = (local[:, None], valid.astype(jnp.float32)[:, None],
                   spec.tp, build_tp_tables(spec.tp))
    kw = dict(n_atom_tiles=T, block_n=spec.block_n,
              block_e=perm.shape[0] // T, interpret=interpret)

    def slots(Y, h_node, R):
        return Y[perm], jnp.swapaxes(h_node[send_b], 1, 2), R[perm]

    def unslot(dY_b, dh_b, dR_b):
        # valid slots are a permutation of the valid edge ids and masked
        # slots carry exact zeros (em gates the gather), so the scatter-add
        # is exact: padding slots only ever add zeros to edge 0
        E = senders.shape[0]
        dY = jnp.zeros((E,) + dY_b.shape[1:], dY_b.dtype).at[perm].add(dY_b)
        dR = jnp.zeros((E,) + dR_b.shape[1:], dR_b.dtype).at[perm].add(dR_b)
        dh = jax.ops.segment_sum(dh_b, send_b, n_atoms)
        return dY, jnp.swapaxes(dh, 1, 2), dR

    def fold(A_t):
        # virtual tiles back onto atom rows: a [T*block_n] segment-add (tile
        # bases repeat for hub atoms and overflow tiles)
        A = jax.ops.segment_sum(A_t, rows, n_atoms + spec.block_n)[:n_atoms]
        return jnp.swapaxes(A, 1, 2) / spec.avg_num_neighbors

    def unfold(g):
        # the per-atom cotangent gathered into tile layout; trash rows read
        # zeros
        gt = jnp.swapaxes(g, 1, 2) / spec.avg_num_neighbors
        pad = jnp.zeros((spec.block_n,) + gt.shape[1:], gt.dtype)
        return jnp.concatenate([gt, pad])[rows]

    def forward(Y_b, h_b, R_b, precision):
        return tp_scatter_pallas_raw(
            Y_b, h_b, R_b, *kernel_args, **kw, precision=precision,
            name="interaction_fwd",
        )

    def backward(G_t, Y_b, h_b, R_b, precision):
        return tp_bwd_pallas_raw(
            G_t, Y_b, h_b, R_b, *kernel_args, **kw, precision=precision,
            name="interaction_bwd",
        )

    return _Layout(slots, unslot, fold, unfold, forward, backward)


def _unblocked_layout(spec, interpret, n_atoms, senders, receivers,
                      edge_mask):
    """Capability fallback: the identity-blocked TP-only kernels and an XLA
    segment-sum over receivers."""
    E = senders.shape[0]
    block_e = 128
    E_p = E + (-E) % block_e
    n_tiles, lr, em = _identity_blocking(E_p, block_e, jnp.float32)
    kernel_args = (lr, em, spec.tp, build_tp_tables(spec.tp))
    kw = dict(n_atom_tiles=n_tiles, block_n=block_e, block_e=block_e,
              interpret=interpret)

    def slots(Y, h_node, R):
        return _block_edge_operands(Y, h_node[senders], R, block_e)[:3]

    def unslot(dY_b, dh_b, dR_b):
        dh = jax.ops.segment_sum(dh_b[:E], senders, n_atoms)
        return dY_b[:E], jnp.swapaxes(dh, 1, 2), dR_b[:E]

    def fold(A_t):
        msgs = jnp.swapaxes(A_t, 1, 2)[:E]
        return aggregate_edge_messages(msgs, receivers, edge_mask, n_atoms,
                                       spec)

    def unfold(g):
        gmsg = (g[receivers] * edge_mask.astype(g.dtype)[:, None, None]
                / spec.avg_num_neighbors)
        return jnp.pad(jnp.swapaxes(gmsg, 1, 2),
                       ((0, E_p - E), (0, 0), (0, 0)))

    def forward(Y_b, h_b, R_b, precision):
        return tp_scatter_pallas_raw(
            Y_b, h_b, R_b, *kernel_args, **kw, precision=precision
        )

    def backward(G_t, Y_b, h_b, R_b, precision):
        return tp_bwd_pallas_raw(
            G_t, Y_b, h_b, R_b, *kernel_args, **kw, precision=precision
        )

    return _Layout(slots, unslot, fold, unfold, forward, backward)


def _float0(a):
    return np.zeros(a.shape, jax.dtypes.float0)


def _second_order(lay: _Layout, g, Y, h_node, R, ct):
    """Exact derivative of the backward op ``B(g, Y, h, R)`` for cotangents
    ``(a, b, c)`` on its outputs ``(dY, dh, dR)``.

    The op is trilinear, ``A = T(Y, h, R)``, and ``B`` returns its three
    partial adjoints, so ``<(a, b, c), B> = <g, T(a, h, R) + T(Y, b, R) +
    T(Y, h, c)>``.  Hence ``ct_g`` is that sum (three forward kernels, summed
    in tile layout and folded once) and each of ``ct_Y``, ``ct_h``, ``ct_R``
    is a sum of two partial adjoints taken from three backward kernels at
    ``(a, h, R)``, ``(Y, b, R)`` and ``(Y, h, c)`` (summed in slot layout and
    un-slotted once).  The gathers into slot layout run once per operand.
    The kernels run at fp32 whatever ``spec.precision`` is."""
    a, b, c = ct
    Y_b, h_b, R_b = lay.slots(Y, h_node, R)
    a_b, b_b, c_b = lay.slots(a, b, c)
    G_t = lay.unfold(g)
    fwd = partial(lay.forward, precision="fp32")
    bwd = partial(lay.backward, G_t, precision="fp32")
    ct_g = lay.fold(fwd(a_b, h_b, R_b) + fwd(Y_b, b_b, R_b)
                    + fwd(Y_b, h_b, c_b))
    _, dh_a, dR_a = bwd(a_b, h_b, R_b)
    dY_b, _, dR_b = bwd(Y_b, b_b, R_b)
    dY_c, dh_c, _ = bwd(Y_b, h_b, c_b)
    return (ct_g,) + lay.unslot(dY_b + dY_c, dh_a + dh_c, dR_a + dR_b)


def _make_pallas_interaction_op(layout):
    """The custom_vjp op over a layout builder ``(spec, interpret, n_atoms,
    senders, receivers, edge_mask, *blocking_arrays) -> _Layout``.

    The backward dispatches on ``spec.bwd_impl``: ``"pallas"`` runs the
    backward kernel through a closed custom_vjp op whose own derivative
    rule is ``_second_order``; ``"xla"`` retains the VJP of the
    numerically-equivalent ``interaction_fused`` formulation.
    Integer/bool operands get float0 cotangents either way."""

    def build(spec, interpret, h_node, ints):
        return layout(spec, interpret, h_node.shape[0], *ints)

    @partial(jax.custom_vjp, nondiff_argnums=(0, 1))
    def bwd_op(spec, interpret, g, Y, h_node, R, *ints):
        lay = build(spec, interpret, h_node, ints)
        return lay.unslot(*lay.backward(
            lay.unfold(g), *lay.slots(Y, h_node, R), spec.precision
        ))

    def bwd_op_fwd(spec, interpret, *args):
        return bwd_op(spec, interpret, *args), args

    def bwd_op_bwd(spec, interpret, res, ct):
        g, Y, h_node, R, *ints = res
        lay = build(spec, interpret, h_node, ints)
        return _second_order(lay, g, Y, h_node, R, ct) + tuple(
            _float0(a) for a in ints
        )

    bwd_op.defvjp(bwd_op_fwd, bwd_op_bwd)

    @partial(jax.custom_vjp, nondiff_argnums=(0, 1))
    def op(spec, interpret, Y, h_node, R, *ints):
        lay = build(spec, interpret, h_node, ints)
        return lay.fold(lay.forward(*lay.slots(Y, h_node, R), spec.precision))

    def fwd(spec, interpret, Y, h_node, R, *ints):
        return op(spec, interpret, Y, h_node, R, *ints), (Y, h_node, R) + ints

    def bwd(spec, interpret, res, g):
        if spec.bwd_impl == "pallas":
            grads = bwd_op(spec, interpret, g, *res)
        else:
            Y, h_node, R, senders, receivers, edge_mask = res[:6]
            _, vjp = jax.vjp(
                lambda y, h, r: interaction_fused(
                    y, h, r, senders, receivers, edge_mask, spec=spec
                ),
                Y, h_node, R,
            )
            grads = vjp(g)
        return tuple(grads) + tuple(_float0(a) for a in res[3:])

    op.defvjp(fwd, bwd)
    return op


_blocked_op = _make_pallas_interaction_op(_blocked_layout)
_unblocked_op = _make_pallas_interaction_op(_unblocked_layout)


def interaction_pallas_op(
    Y: jnp.ndarray,
    h_node: jnp.ndarray,
    R: jnp.ndarray,
    senders: jnp.ndarray,
    receivers: jnp.ndarray,
    edge_mask: jnp.ndarray,
    *,
    spec: InteractionSpec,
    blocking: Optional[Dict[str, jnp.ndarray]] = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Registered ``interaction/pallas`` impl (see module docstring)."""
    if blocking is None:
        return _unblocked_op(
            spec, interpret, Y, h_node, R, senders, receivers, edge_mask
        )
    if blocking["perm"].shape[0] % blocking["base"].shape[0]:
        raise ValueError("blocking perm length not a multiple of tile count")
    return _blocked_op(
        spec, interpret, Y, h_node, R, senders, receivers, edge_mask,
        blocking["perm"], blocking["valid"], blocking["local"],
        blocking["base"],
    )


def interaction_pallas(
    Y: jnp.ndarray,
    h_node: jnp.ndarray,
    R: jnp.ndarray,
    senders: jnp.ndarray,
    receivers: jnp.ndarray,
    edge_mask: jnp.ndarray,
    blocking: EdgeBlocking,
    spec: InteractionSpec,
    *,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Convenience wrapper taking a host-side :class:`EdgeBlocking`."""
    if blocking.block_n != spec.block_n:
        raise ValueError(
            f"blocking block_n={blocking.block_n} != spec.block_n={spec.block_n}"
        )
    arrays = {
        "perm": jnp.asarray(blocking.perm, jnp.int32),
        "valid": jnp.asarray(blocking.valid),
        "local": jnp.asarray(blocking.local_rcv),
        "base": jnp.asarray(blocking.tile_base),
    }
    return interaction_pallas_op(
        Y, h_node, R, senders, receivers, edge_mask,
        spec=spec, blocking=arrays, interpret=interpret,
    )
