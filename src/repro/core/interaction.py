"""The interaction op: channelwise TP + receiver scatter + neighbor norm.

This is the edge->atom stage of a MACE interaction layer as ONE operation,

    A_i = (1 / avg_num_neighbors) * sum_{j in N(i)} TP(Y_ji, h_j, R_ji)

registered under the ``"interaction"`` kind in ``kernels.registry`` with
three implementations:

``ref``
    ``tp_ref`` (per-path dense-CG einsums) -> mask -> ``segment_sum`` — the
    oracle, and exactly the pre-refactor aggregation path of ``core/mace``.
``fused``
    Aggregates in the *nnz basis*: per-edge CG contributions ``[E, k, nnz]``
    (the same tensor ``tp_fused`` already builds) are segment-summed
    straight to atoms and only then projected to ``dim_out`` with the
    compile-time one-hot m3 matrix.  Because the projection commutes with
    the (linear) pooling, this never materializes the ``[E, k, d_out]``
    message tensor of the TP -> scatter pipeline (§4; cf. arXiv
    2211.13853) and moves the m3 matmul from E rows to N rows.  Note the
    per-edge ``[E, k, nnz]`` contribution tensor remains — and nnz can
    exceed d_out — so this is a *partial* dematerialization at the XLA
    level; eliminating per-edge HBM traffic altogether is exactly what the
    on-chip ``pallas`` kernel is for.
``pallas`` (in ``kernels/channelwise_tp/ops.py``)
    The TPU kernel: TP and scatter fused on-chip over pre-blocked edges from
    the data pipeline (``data.blocking``), with a capability fallback to the
    TP-only kernel + XLA segment-sum when no blocking metadata is present.

All impls share one signature (bound to an :class:`InteractionSpec` by the
registry):

    fn(Y, h_node, R, senders, receivers, edge_mask, *, blocking=None) -> A

with ``Y [E, dim_sh]``, ``h_node [N, k, dim_h]`` (gathered to edges inside
the op), ``R [E, n_paths, k]``, and ``A [N, k, dim_out]``.  ``blocking`` is
the optional array half of the data-pipeline blocking contract
(``data.blocking.blocking_from_batch``); ref/fused ignore it.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from .channelwise_tp import (
    TPSpec,
    TPTables,
    build_tp_tables,
    cg_scatter_matrix,
    tp_contrib,
    tp_ref,
)


@dataclasses.dataclass(frozen=True)
class InteractionSpec:
    """Static description of one interaction op (hashable: registry key)."""

    tp: TPSpec
    avg_num_neighbors: float
    # atom rows per kernel tile; must equal the data pipeline's
    # BinShape.block_n when blocking metadata is consumed (Trainer validates)
    block_n: int = 32
    # backward implementation for custom_vjp-carrying impls: "pallas" runs
    # the dedicated gather + TP-transpose backward kernel sharing the
    # forward's tile geometry, and its own derivative (the second order)
    # on the same two kernels; "xla" retains the fused-XLA formulation's
    # VJP (the capability fallback).  Impls without a custom backward
    # ignore it.
    bwd_impl: str = "pallas"
    # compute precision of the pallas kernels (fwd + hand-written bwd):
    # reduced precisions round operand tile loads, accumulation stays fp32
    # (repro.kernels.precision).  ref/fused impls ignore it (always fp32);
    # the second-order pass stays fp32 at every setting.
    precision: str = "fp32"

    def __post_init__(self):
        if self.bwd_impl not in ("pallas", "xla"):
            raise ValueError(
                f"bwd_impl must be 'pallas' or 'xla', got {self.bwd_impl!r}"
            )
        from repro.kernels.precision import check_precision

        check_precision(self.precision)


def resolve_interaction(name: str, spec: InteractionSpec):
    """Resolve an interaction impl by name through ``kernels.registry``.

    Third-party backends may register a *TP-only* kernel under the
    ``channelwise_tp`` kind (the registry's documented extension point)
    without providing a matching ``interaction`` impl; such a name falls
    back to that TP impl wrapped in the oracle aggregation (gather ->
    mask -> segment_sum -> /avg), so ``MaceConfig(impl="<registered>")``
    keeps working model-wide.
    """
    from repro.kernels import registry  # deferred: keep core importable solo

    # check *registration* first so a KeyError raised inside a registered
    # builder (a real bug) propagates instead of silently selecting the
    # TP-only fallback path
    if name in registry.available("interaction"):
        return registry.resolve("interaction", name, spec)
    if name not in registry.available("channelwise_tp"):
        raise KeyError(
            f"no interaction or channelwise_tp impl {name!r}; "
            f"interaction: {registry.available('interaction')}, "
            f"channelwise_tp: {registry.available('channelwise_tp')}"
        )
    tp_fn = registry.resolve("channelwise_tp", name, spec.tp)

    def tp_wrapped(Y, h_node, R, senders, receivers, edge_mask, *,
                   blocking=None):
        del blocking
        msgs = tp_fn(Y, h_node[senders], R)
        return aggregate_edge_messages(
            msgs, receivers, edge_mask, h_node.shape[0], spec
        )

    return tp_wrapped


def aggregate_edge_messages(
    msgs: jnp.ndarray,       # [E, k, d] per-edge messages (any basis)
    receivers: jnp.ndarray,  # [E] int32
    edge_mask: jnp.ndarray,  # [E] bool
    n_atoms: int,
    spec: InteractionSpec,
) -> jnp.ndarray:
    """The one copy of the aggregation tail every decomposed interaction
    path shares: mask -> segment_sum over receivers -> /avg_num_neighbors."""
    msgs = msgs * edge_mask.astype(msgs.dtype)[:, None, None]
    return jax.ops.segment_sum(msgs, receivers, n_atoms) / spec.avg_num_neighbors


def interaction_ref(
    Y: jnp.ndarray,          # [E, dim_sh]
    h_node: jnp.ndarray,     # [N, k, dim_h]
    R: jnp.ndarray,          # [E, n_paths, k]
    senders: jnp.ndarray,    # [E] int32
    receivers: jnp.ndarray,  # [E] int32
    edge_mask: jnp.ndarray,  # [E] bool
    *,
    spec: InteractionSpec,
    blocking=None,
) -> jnp.ndarray:
    """Oracle: e3nn-style TP -> [E, k, d_out] messages -> segment_sum."""
    del blocking  # dense path has no use for pre-blocked edges
    msgs = tp_ref(Y, h_node[senders], R, spec.tp)
    return aggregate_edge_messages(
        msgs, receivers, edge_mask, h_node.shape[0], spec
    )


def interaction_fused(
    Y: jnp.ndarray,
    h_node: jnp.ndarray,
    R: jnp.ndarray,
    senders: jnp.ndarray,
    receivers: jnp.ndarray,
    edge_mask: jnp.ndarray,
    *,
    spec: InteractionSpec,
    tables: TPTables | None = None,
    blocking=None,
) -> jnp.ndarray:
    """nnz-basis aggregation: no [E, k, d_out] message tensor (the
    [E, k, nnz] CG-contribution tensor shared with ``tp_fused`` remains;
    see the module docstring for what that does and does not buy)."""
    del blocking
    t = tables if tables is not None else build_tp_tables(spec.tp)
    n_atoms = h_node.shape[0]
    contrib = tp_contrib(Y, h_node[senders], R, t)        # [E, k, nnz]
    contrib = contrib * edge_mask.astype(contrib.dtype)[:, None, None]
    pre = jax.ops.segment_sum(contrib, receivers, n_atoms)  # [N, k, nnz]
    A = pre @ cg_scatter_matrix(t, pre.dtype)               # [N, k, d_out]
    return A / spec.avg_num_neighbors
