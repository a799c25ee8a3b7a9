"""Plain MACE: the reference that decides whether a training cell is correct.

MACE (Batatia et al., NeurIPS 2022) as the benchmark's configurations state
it, in straightforward ``jax.numpy`` and float32, with no kernels, tables or
tiling: every channelwise tensor-product path is a dense CG einsum, and the
symmetric contraction multiplies the outer powers of A by the dense U tensor
(one matmul per (L, nu) term).  It imports nothing of the program under
test; ``cg.py`` beside it is its own copy of the CG and U numerics.

Batches come from ``collate`` here: graphs laid out block-diagonally and
padded to fixed sizes, so that the three reference steps share one compiled
program.  Padded atoms and edges are masked out of every sum.

Departures from the published model, each also the program's definition:
the atomic-basis normalisation is one constant ``avg_num_neighbors``; the
last readout is a one-hidden-layer SiLU MLP.  The loss is MACE's weighted
energy and forces loss: the squared energy error per atom averaged over the
batch's real graphs, and the squared force error averaged over its real
atoms and the three components.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import cg

Params = Dict


# ----------------------------- sizes ---------------------------------------


@dataclasses.dataclass(frozen=True)
class Sizes:
    """The numbers of a MACE configuration that the reference needs."""

    n_species: int
    channels: int
    hidden_ls: Tuple[int, ...]
    sh_lmax: int
    a_ls: Tuple[int, ...]
    correlation: int
    n_interactions: int
    r_max: float
    num_bessel: int
    radial_mlp: Tuple[int, ...]
    readout_mlp: int
    avg_num_neighbors: float

    @staticmethod
    def of(obj) -> "Sizes":
        """Sizes read off any object or mapping with these field names."""
        get = (obj.get if isinstance(obj, dict)
               else lambda name: getattr(obj, name))
        kw = {}
        for f in dataclasses.fields(Sizes):
            v = get(f.name)
            kw[f.name] = tuple(int(x) for x in v) if isinstance(
                v, (list, tuple)) else v
        return Sizes(**kw)

    @property
    def sh_ls(self) -> Tuple[int, ...]:
        return tuple(range(self.sh_lmax + 1))

    def h_ls(self, layer: int) -> Tuple[int, ...]:
        return (0,) if layer == 0 else self.hidden_ls


def dim(ls: Sequence[int]) -> int:
    return sum(2 * l + 1 for l in ls)


def slices(ls: Sequence[int]) -> List[Tuple[int, slice]]:
    out, off = [], 0
    for l in ls:
        out.append((l, slice(off, off + 2 * l + 1)))
        off += 2 * l + 1
    return out


def slice_for(ls: Sequence[int], l: int) -> slice:
    return dict(slices(ls))[l]


def tp_paths(y_ls, h_ls, out_ls) -> List[Tuple[int, int, int]]:
    """Allowed (l1, l2, l3) paths, l3-major: the order of the weights."""
    return [(l1, l2, l3) for l3 in out_ls for l1 in y_ls for l2 in h_ls
            if cg.parity_ok(l1, l2, l3)]


def symcon_terms(a_ls, out_ls, nu_max) -> List[Tuple[int, int]]:
    return [(L, nu) for L in out_ls for nu in range(1, nu_max + 1)
            if cg.u_tensor(tuple(a_ls), L, nu).shape[-1] > 0]


# ----------------------------- init ----------------------------------------


def _normal(key, shape, scale):
    return jax.random.normal(key, shape, jnp.float32) / np.sqrt(scale)


def _linear_per_l(key, ls, k):
    keys = jax.random.split(key, len(ls))
    return {f"l{l}_{i}": _normal(keys[i], (k, k), k) for i, l in enumerate(ls)}


def _mlp(key, sizes):
    keys = jax.random.split(key, len(sizes) - 1)
    p = {}
    for i, (a, b) in enumerate(zip(sizes[:-1], sizes[1:])):
        p[f"w{i}"] = _normal(keys[i], (a, b), a)
        p[f"b{i}"] = jnp.zeros((b,), jnp.float32)
    return p


def init_params(key, s: Sizes) -> Params:
    """The weights of a seed, drawn in MACE's documented order."""
    k = s.channels
    keys = iter(jax.random.split(key, 8 + 10 * s.n_interactions))
    params: Params = {
        "embed": _normal(next(keys), (s.n_species, k), s.n_species),
        "e0": jnp.zeros((s.n_species,), jnp.float32),
    }
    for t in range(s.n_interactions):
        h_ls = s.h_ls(t)
        n_paths = len(tp_paths(s.sh_ls, h_ls, s.a_ls))
        layer = {
            "lin_up": _linear_per_l(next(keys), h_ls, k),
            "radial": _mlp(next(keys), (s.num_bessel, *s.radial_mlp, n_paths * k)),
            "lin_a": _linear_per_l(next(keys), s.a_ls, k),
        }
        terms = sorted(symcon_terms(s.a_ls, s.hidden_ls, s.correlation))
        sk = jax.random.split(next(keys), max(len(terms), 1))
        layer["symcon"] = {}
        for i, (L, nu) in enumerate(terms):
            P = cg.u_tensor(tuple(s.a_ls), L, nu).shape[-1]
            layer["symcon"][f"w_L{L}_nu{nu}"] = _normal(
                sk[i], (s.n_species, k, P), P)
        layer["lin_msg"] = _linear_per_l(next(keys), s.hidden_ls, k)
        layer["skip"] = {
            f"l{l}_{i}": _normal(next(keys), (s.n_species, k, k), k)
            for i, l in enumerate(h_ls) if l in s.hidden_ls
        }
        if t < s.n_interactions - 1:
            layer["readout"] = _normal(next(keys), (k, 1), k)
        else:
            layer["readout_mlp"] = _mlp(next(keys), (k, s.readout_mlp, 1))
        params[f"layer_{t}"] = layer
    return params


# ----------------------------- forward -------------------------------------


def _int_pow(t, p):
    out = jnp.ones_like(t)
    for _ in range(p):
        out = out * t
    return out


def spherical_harmonics(lmax: int, vec):
    """Real SH, l = 0..lmax, normalised so that Y_00 = 1: [E, dim]."""
    n = jnp.sqrt(jnp.maximum(jnp.sum(vec * vec, -1, keepdims=True), 1e-18))
    v = vec / n
    x, y, z = v[..., 0], v[..., 1], v[..., 2]
    blocks = []
    for l in range(lmax + 1):
        coeffs = jnp.asarray(cg.real_sh_polys(l), vec.dtype)
        monos = jnp.stack([_int_pow(x, a) * _int_pow(y, b) * _int_pow(z, c)
                           for (a, b, c) in cg.monomial_exponents(l)], -1)
        blocks.append(monos @ coeffs.T)
    return jnp.concatenate(blocks, -1)


def radial_basis(r, r_max: float, num: int, p: int = 6):
    """Bessel functions times MACE's polynomial cutoff (p = 6): [E, num]."""
    n = jnp.arange(1, num + 1, dtype=r.dtype)
    x = jnp.where(r > 1e-9, r, 1e-9)[:, None]
    bessel = jnp.sqrt(2.0 / r_max) * jnp.sin(n * jnp.pi * x / r_max) / x
    u = r / r_max
    env = (1.0 - (p + 1.0) * (p + 2.0) / 2.0 * u**p + p * (p + 2.0) * u ** (p + 1)
           - p * (p + 1.0) / 2.0 * u ** (p + 2)) * (u < 1.0)
    return bessel * env[:, None]


def _mlp_apply(p, x):
    n = len(p) // 2
    for i in range(n):
        x = x @ p[f"w{i}"] + p[f"b{i}"]
        if i < n - 1:
            x = jax.nn.silu(x)
    return x


def _linear_apply(p, x, ls):
    return jnp.concatenate(
        [jnp.einsum("nkd,kq->nqd", x[:, :, sl], p[f"l{l}_{i}"])
         for i, (l, sl) in enumerate(slices(ls))], -1)


def interaction(Y, h, R, senders, receivers, edge_mask, s: Sizes, layer: int):
    """A_i = sum over edges j->i of the channelwise TP, / avg_num_neighbors."""
    h_ls = s.h_ls(layer)
    hs = h[senders]                                   # [E, k, dim_h]
    E, k = hs.shape[:2]
    msgs = jnp.zeros((E, k, dim(s.a_ls)), hs.dtype)
    for p, (l1, l2, l3) in enumerate(tp_paths(s.sh_ls, h_ls, s.a_ls)):
        C = jnp.asarray(cg.real_cg(l1, l2, l3), hs.dtype)
        blk = jnp.einsum("abc,ea,ekb->ekc", C, Y[:, slice_for(s.sh_ls, l1)],
                         hs[:, :, slice_for(h_ls, l2)]) * R[:, p, :, None]
        msgs = msgs.at[:, :, slice_for(s.a_ls, l3)].add(blk)
    msgs = msgs * edge_mask[:, None, None]
    return jax.ops.segment_sum(msgs, receivers, h.shape[0]) / s.avg_num_neighbors


def symmetric_contraction(A, species, w, s: Sizes):
    """B = sum over (L, nu) of W . (U contracted with the nu-th outer power
    of A), with the dense U tensor as one matmul per term."""
    N, k, d = A.shape
    out = jnp.zeros((N, k, dim(s.hidden_ls)), A.dtype)
    for (L, nu) in symcon_terms(s.a_ls, s.hidden_ls, s.correlation):
        U = cg.u_tensor(tuple(s.a_ls), L, nu)        # [d]*nu + [2L+1, P]
        P = U.shape[-1]
        outer = A
        for _ in range(nu - 1):
            outer = (outer[..., :, None] * A[..., None, :]).reshape(N, k, -1)
        Um = jnp.asarray(U.reshape(d**nu, (2 * L + 1) * P), A.dtype)
        q = (outer @ Um).reshape(N, k, 2 * L + 1, P)
        W = w[f"w_L{L}_nu{nu}"][species]              # [N, k, P]
        out = out.at[:, :, slice_for(s.hidden_ls, L)].add(
            jnp.einsum("nkmp,nkp->nkm", q, W))
    return out


def energies(params, s: Sizes, b):
    """Per-graph energies [n_graph_slots] of a collated batch ``b``."""
    species, pos = b["species"], b["positions"]
    nmask = b["node_mask"]
    vec = pos[b["receivers"]] - pos[b["senders"]]
    r = jnp.sqrt(jnp.sum(vec * vec, -1) + 1e-18)
    Y = spherical_harmonics(s.sh_lmax, vec)
    rad = radial_basis(r, s.r_max, s.num_bessel)
    k = s.channels
    h = params["embed"][species][:, :, None] * nmask[:, None, None]
    site = jnp.zeros(species.shape, pos.dtype)
    for t in range(s.n_interactions):
        lp = params[f"layer_{t}"]
        h_ls = s.h_ls(t)
        n_paths = len(tp_paths(s.sh_ls, h_ls, s.a_ls))
        R = _mlp_apply(lp["radial"], rad).reshape(-1, n_paths, k)
        A = interaction(Y, _linear_apply(lp["lin_up"], h, h_ls), R,
                        b["senders"], b["receivers"], b["edge_mask"], s, t)
        A = _linear_apply(lp["lin_a"], A, s.a_ls)
        B = symmetric_contraction(A, species, lp["symcon"], s)
        m = _linear_apply(lp["lin_msg"], B, s.hidden_ls)
        skip = jnp.zeros_like(m)
        for i, (l, sl_h) in enumerate(slices(h_ls)):
            if l in s.hidden_ls:
                W = lp["skip"][f"l{l}_{i}"][species]
                skip = skip.at[:, :, slice_for(s.hidden_ls, l)].add(
                    jnp.einsum("nkd,nkq->nqd", h[:, :, sl_h], W))
        h = (m + skip) * nmask[:, None, None]
        inv = h[:, :, slice_for(s.hidden_ls, 0)][:, :, 0]
        if t < s.n_interactions - 1:
            e_t = (inv @ lp["readout"])[:, 0]
        else:
            e_t = _mlp_apply(lp["readout_mlp"], inv)[:, 0]
        site = site + e_t * nmask
    site = site + params["e0"][species] * nmask
    return jax.ops.segment_sum(site, b["graph_id"], b["energy"].shape[0])


def loss(params, s: Sizes, b, energy_weight: float, forces_weight: float):
    """Energy-per-atom and force squared errors, weighted as configured."""
    def total(pos):
        e = energies(params, s, dict(b, positions=pos))
        return jnp.sum(e), e

    grad, e = jax.grad(total, has_aux=True)(b["positions"])
    forces = -grad * b["node_mask"][:, None]
    nat = jnp.maximum(jax.ops.segment_sum(b["node_mask"], b["graph_id"],
                                          e.shape[0]), 1.0)
    e_err = jnp.sum(((e - b["energy"]) / nat) ** 2 * b["graph_mask"])
    f_err = jnp.sum(jnp.sum((forces - b["forces"]) ** 2, -1) * b["node_mask"])
    n_graphs = jnp.maximum(jnp.sum(b["graph_mask"]), 1.0)
    return (energy_weight * e_err / n_graphs
            + forces_weight * f_err / (3.0 * b["n_atoms"]))


# ----------------------------- batches -------------------------------------


def collate(mols, n_nodes: int, n_edges: int, n_graph_slots: int):
    """Block-diagonal layout of ``mols`` padded to fixed sizes (numpy)."""
    species = np.zeros(n_nodes, np.int32)
    pos = np.zeros((n_nodes, 3), np.float32)
    nmask = np.zeros(n_nodes, np.float32)
    gid = np.full(n_nodes, n_graph_slots, np.int32)
    snd = np.zeros(n_edges, np.int32)
    rcv = np.zeros(n_edges, np.int32)
    emask = np.zeros(n_edges, np.float32)
    energy = np.zeros(n_graph_slots + 1, np.float32)
    gmask = np.zeros(n_graph_slots + 1, np.float32)
    forces = np.zeros((n_nodes, 3), np.float32)
    n_off = e_off = 0
    for g, m in enumerate(mols):
        n, e = len(m.species), len(m.senders)
        if n_off + n > n_nodes or e_off + e > n_edges or g >= n_graph_slots:
            raise ValueError("graphs do not fit the reference's batch")
        species[n_off:n_off + n] = m.species
        pos[n_off:n_off + n] = m.positions
        nmask[n_off:n_off + n] = 1.0
        gid[n_off:n_off + n] = g
        forces[n_off:n_off + n] = m.forces
        snd[e_off:e_off + e] = m.senders + n_off
        rcv[e_off:e_off + e] = m.receivers + n_off
        emask[e_off:e_off + e] = 1.0
        energy[g], gmask[g] = m.energy, 1.0
        n_off += n
        e_off += e
    return {"species": species, "positions": pos, "node_mask": nmask,
            "graph_id": gid, "senders": snd, "receivers": rcv,
            "edge_mask": emask, "energy": energy, "graph_mask": gmask,
            "forces": forces, "n_atoms": np.float32(max(n_off, 1))}


# ----------------------------- training ------------------------------------


def clip_and_adam(lr, clip_norm, b1=0.9, b2=0.999, eps=1e-8):
    """Global-norm clipping then Adam: ``step(params, grads, state, t)``
    returns ``(params, state, clipped_grads)``."""
    def step(params, grads, state, t):
        leaves = jax.tree.leaves(grads)
        gn = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
        scale = jnp.minimum(1.0, clip_norm / jnp.maximum(gn, 1e-12))
        g = jax.tree.map(lambda x: x * scale, grads)
        m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, state["m"], g)
        v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, state["v"], g)
        c1, c2 = 1 - b1 ** (t + 1.0), 1 - b2 ** (t + 1.0)
        new = jax.tree.map(
            lambda p, a, b: p - lr * (a / c1) / (jnp.sqrt(b / c2) + eps),
            params, m, v)
        return new, {"m": m, "v": v}, g
    return step


def train_steps(seed: int, s: Sizes, steps, *, lr, clip_norm,
                energy_weight, forces_weight, dtype: str = "float32"):
    """Run the reference from the seed's weights through ``steps``, each a
    list of batches, one per data-parallel rank: a step's loss and gradient
    are the means over its ranks, as the all-reduce makes them.

    Returns ``(losses, first_grads, params0, params_last)``: each step's
    loss, the first step's gradient after clipping (what the optimizer is
    given), and the weights before the first and after the last step.
    ``dtype`` other than float32 computes the loss and its gradients with
    the weights and the batch in that type (the control); the optimizer
    stays in float32."""
    params = init_params(jax.random.PRNGKey(seed), s)
    p0 = params
    dt = jnp.dtype(dtype)

    def lower(tree):
        return jax.tree.map(
            lambda x: x.astype(dt) if jnp.issubdtype(x.dtype, jnp.floating)
            else x, tree)

    def loss32(p, b):
        return loss(lower(p), s, lower(b), energy_weight,
                    forces_weight).astype(jnp.float32)

    vg = jax.jit(jax.value_and_grad(loss32))
    opt = jax.jit(clip_and_adam(lr, clip_norm))
    state = {"m": jax.tree.map(jnp.zeros_like, params),
             "v": jax.tree.map(jnp.zeros_like, params)}
    losses, g1 = [], None
    with jax.default_matmul_precision("highest"):
        for t, ranks in enumerate(steps):
            outs = [vg(params, {k: jnp.asarray(v) for k, v in b.items()})
                    for b in ranks]
            val = sum(float(o[0]) for o in outs) / len(outs)
            grads = jax.tree.map(lambda *g: sum(g) / len(g), *[o[1] for o in outs])
            params, state, g = opt(params, grads, state, jnp.float32(t))
            losses.append(val)
            if g1 is None:
                g1 = g
    return losses, g1, p0, params


def leaf_norms(tree) -> Dict[str, float]:
    """Frobenius norm of every leaf, keyed by its path."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(k): float(math.sqrt(float(jnp.sum(
        jnp.asarray(v, jnp.float32) ** 2)))) for k, v in flat}
