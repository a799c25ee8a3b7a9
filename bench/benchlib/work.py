"""Operations and bytes of MACE's work, from the shapes of each call.

The counts are the algorithm's own and the same whatever implements an op:

* ``symcon``: the symmetric contraction's sparse U-tensor nonzeros per
  (L, nu) term, counted as the program's ``symcon_flops`` counts them
  (products, weight and value multiplies, and the (2L+1)-wide scatter);
* ``interaction``: the channelwise tensor product's CG nonzeros over all
  paths (4 operations each: Y*val, *h, *R, accumulate) plus the scatter of
  the d_out-wide result;
* bytes: every input, weight and output moved once between HBM and the
  chip, in float32 (4 bytes), with int32 indices.

A call's least time is the larger of its operations over the peak FLOP/s
and its bytes over the peak bandwidth (``least_time``).  Real atoms and
edges are counted, never the padding of a bin.
"""
from __future__ import annotations

import functools
import os
import sys
from typing import Dict, Tuple

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from reference import cg  # noqa: E402
from reference.mace import Sizes, dim, symcon_terms, tp_paths  # noqa: E402

F32 = 4
I32 = 4

# A training step with forces in the loss, per forward pass F of the model:
# the forward itself (F); the force pass, the gradient of the energy with
# respect to the positions, about twice a forward (2F), as any reverse pass
# is; and the parameter gradient, a reverse pass through both of those
# graphs, about twice their sum (2 * 3F = 6F).  Total 9F.
TRAIN_FACTOR = 9.0

# The reverse pass of a multilinear op costs about twice its forward: each
# multiply-accumulate of the forward yields one for each operand's
# cotangent.
BWD_FACTOR = 2.0


@functools.lru_cache(maxsize=None)
def symcon_nnz(a_ls: Tuple[int, ...], out_ls: Tuple[int, ...], nu_max: int):
    """[(L, nu, nnz)] of the U tensors' nonzeros."""
    return [(L, nu, len(cg.u_tensor_nonzeros(a_ls, L, nu)[3]))
            for (L, nu) in symcon_terms(a_ls, out_ls, nu_max)]


@functools.lru_cache(maxsize=None)
def symcon_paths(a_ls: Tuple[int, ...], out_ls: Tuple[int, ...], nu_max: int):
    return sum(cg.u_tensor(a_ls, L, nu).shape[-1]
               for (L, nu) in symcon_terms(a_ls, out_ls, nu_max))


@functools.lru_cache(maxsize=None)
def tp_nnz(y_ls: Tuple[int, ...], h_ls: Tuple[int, ...],
           out_ls: Tuple[int, ...]) -> int:
    return sum(len(cg.cg_nonzeros(l1, l2, l3))
               for (l1, l2, l3) in tp_paths(y_ls, h_ls, out_ls))


def symcon_ops(s: Sizes, n_atoms: float) -> float:
    total = 0.0
    for (L, nu, nnz) in symcon_nnz(s.a_ls, s.hidden_ls, s.correlation):
        total += n_atoms * s.channels * nnz * (nu - 1 + 2)
        total += n_atoms * s.channels * nnz * (2 * L + 1) * 2
    return total


def symcon_call(s: Sizes, n_atoms: float, backward: bool) -> Tuple[float, float]:
    """(ops, bytes) of one symmetric-contraction call on ``n_atoms``."""
    k = s.channels
    a_b = n_atoms * k * dim(s.a_ls) * F32
    out_b = n_atoms * k * dim(s.hidden_ls) * F32
    w_b = s.n_species * k * symcon_paths(s.a_ls, s.hidden_ls, s.correlation) * F32
    ops = symcon_ops(s, n_atoms)
    if not backward:
        return ops, a_b + w_b + out_b + n_atoms * I32
    # reads the cotangent of B, A and W; writes those of A and W
    return BWD_FACTOR * ops, out_b + 2 * a_b + 2 * w_b + n_atoms * I32


def interaction_call(s: Sizes, layer: int, n_atoms: float, n_edges: float,
                     backward: bool) -> Tuple[float, float]:
    """(ops, bytes) of one interaction call (TP, scatter to receivers) of
    ``layer`` on ``n_atoms`` atoms and ``n_edges`` edges."""
    k = s.channels
    h_ls = s.h_ls(layer)
    d_out = dim(s.a_ls)
    nnz = tp_nnz(s.sh_ls, h_ls, s.a_ls)
    n_paths = len(tp_paths(s.sh_ls, h_ls, s.a_ls))
    ops = n_edges * k * (4.0 * nnz + 2.0 * d_out)
    y_b = n_edges * dim(s.sh_ls) * F32
    h_b = n_atoms * k * dim(h_ls) * F32
    r_b = n_edges * n_paths * k * F32
    out_b = n_atoms * k * d_out * F32
    idx_b = 2 * n_edges * I32
    if not backward:
        return ops, y_b + h_b + r_b + out_b + idx_b
    # reads the cotangent of A and the three operands; writes their cotangents
    return BWD_FACTOR * ops, out_b + 2 * (y_b + h_b + r_b) + idx_b


def forward_flops(s: Sizes, n_atoms: float, n_edges: float) -> float:
    """Operations of one forward pass of the model (energies only)."""
    k = s.channels
    total = 0.0
    for t in range(s.n_interactions):
        h_ls = s.h_ls(t)
        n_paths = len(tp_paths(s.sh_ls, h_ls, s.a_ls))
        total += interaction_call(s, t, n_atoms, n_edges, False)[0]
        widths = (s.num_bessel, *s.radial_mlp, n_paths * k)
        total += sum(2.0 * n_edges * a * b for a, b in zip(widths, widths[1:]))
        total += 2.0 * n_atoms * k * k * (dim(h_ls) + dim(s.a_ls)
                                          + dim(s.hidden_ls))
        total += symcon_ops(s, n_atoms)
        total += sum(2.0 * n_atoms * k * k * (2 * l + 1)
                     for l in h_ls if l in s.hidden_ls)
        if t < s.n_interactions - 1:
            total += 2.0 * n_atoms * k
        else:
            total += 2.0 * n_atoms * (k * s.readout_mlp + s.readout_mlp)
    return total


def train_step_flops(s: Sizes, n_atoms: float, n_edges: float) -> float:
    return TRAIN_FACTOR * forward_flops(s, n_atoms, n_edges)


def least_time(ops: float, nbytes: float, peaks: Dict) -> Tuple[float, str]:
    """(seconds, 'compute' | 'memory'): the chip's least time for the work."""
    t_c = ops / peaks["flops_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
