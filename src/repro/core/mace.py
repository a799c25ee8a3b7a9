"""MACE (Batatia et al., NeurIPS 2022) in pure JAX.

Faithful to the paper-under-reproduction's configuration (§5.2): 2 interaction
layers, hidden irreps 128x0e+128x1o, spherical harmonics l<=3, correlation
order nu (2 by default per the paper; 3 supported = MACE's own default), 8
Bessel functions, polynomial cutoff, Adam-friendly fp32.

Structure per interaction layer t:
  1. per-l linear "up" on node features h
  2. radial MLP -> per-path x per-channel TP weights  R_{ji,k,(l1l2l3)}
  3. interaction op (one call through ``kernels.registry``): channelwise
     tensor product (Algorithm 2) + masked scatter-sum over receivers
     + /avg_num_neighbors  ->  atomic basis A_i
  4. per-l linear on A
  5. symmetric contraction (Algorithm 3)  ->  higher-body-order B_i
  6. message m = per-l linear(B);  h' = m + species-dependent skip(h)
  7. readout: layer < last: linear on invariant block; last: MLP

Total energy  E = sum_i (E0_{z_i} + sum_t readout_t(h_i^t));
forces  F = -dE/dr  via jax.grad (tests check rotational equivariance).

Each part runs under a ``jax.named_scope`` that lands in its HLO ops'
metadata: ``edge_geometry``, ``embedding``, ``radial``, ``interaction``,
``linear``, ``symcon``, ``skip``, ``readout`` and ``loss``.  Differentiation
wraps the scope in its transforms (``transpose(jvp(symcon))``), so a trace
reader can time each part in each pass whatever implements it.

Batch layout (static shapes; padding masked):
  species    [N] int32   (padded entries arbitrary, masked by node_mask)
  positions  [N, 3]
  node_mask  [N] bool
  senders    [E] int32   (padded edges self-loop node 0, masked)
  receivers  [E] int32
  edge_mask  [E] bool
  graph_id   [N] int32   (which graph a node belongs to; < n_graphs)
  n_graphs   static int

Optional blocking metadata (the fused TP+scatter kernel's batch contract,
emitted by ``data/collate.py`` when the selected interaction impl consumes
it — see ``data.blocking``):
  blk_perm   [T*epb] int32   edge permutation into receiver-sorted tiles
  blk_valid  [T*epb] bool
  blk_local  [T*epb] int32   receiver offset within the tile
  blk_base   [T] int32       first atom row covered by each tile
``MaceConfig.interaction_block_n`` must equal the pipeline's
``BinShape.block_n`` (one static value that cannot travel in an array).

Training differentiates through the same registry-resolved calls: the
pallas impls carry hand-written backward kernels via ``jax.custom_vjp``
(registry capability ``has_custom_bwd``), and
``MaceConfig.interaction_bwd_impl`` selects the interaction backward
("pallas" = dedicated blocked-gather + TP-transpose kernel, "xla" = the
fused formulation's VJP fallback).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.registry import resolve

from .channelwise_tp import TPSpec
from .interaction import InteractionSpec, resolve_interaction
from .irreps import LSpec, lspec, sh_spec
from .radial import apply_mlp, init_mlp, radial_embedding
from .spherical import spherical_harmonics
from .symmetric_contraction import SymConSpec, init_symcon_weights

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class MaceConfig:
    n_species: int = 10
    channels: int = 128                   # k
    hidden_ls: Tuple[int, ...] = (0, 1)   # 128x0e + 128x1o
    sh_lmax: int = 3
    a_ls: Tuple[int, ...] = (0, 1, 2, 3)  # atomic-basis irreps
    correlation: int = 2                  # nu_max (paper §5.2)
    n_interactions: int = 2
    r_max: float = 4.5
    num_bessel: int = 8
    radial_mlp: Tuple[int, ...] = (64, 64, 64)
    readout_mlp: int = 16
    avg_num_neighbors: float = 12.0
    # contraction impl for symcon + channelwise_tp: any name in
    # kernels.registry ("ref" | "fused" | "pallas" | registered), or the
    # "auto" sentinel — resolved against the committed tuning table by the
    # engine/Trainer build path (``kernels.autotune.resolve_mace_config``)
    # before the model is instantiated; a raw ``init_mace``/``mace_apply``
    # caller must pass a concrete name.
    impl: str = "fused"
    # interaction (TP+scatter) impl; "auto" follows ``impl`` at the raw
    # model level (legacy behavior, see ``interaction_impl_name``), but the
    # engine/Trainer build path intercepts it first and resolves it from
    # the tuning table (impl + tile geometry + bwd_impl).  Selecting
    # "pallas" consumes the data pipeline's blk_* batch arrays when present
    # and falls back to TP-kernel + segment_sum when absent.
    interaction_impl: str = "auto"
    # backward impl for custom-VJP interaction kernels: "pallas" = the
    # dedicated blocked-gather + TP-transpose backward kernel (default;
    # grad-of-grad runs on the same kernels), "xla" = the fused-XLA
    # formulation's VJP (capability fallback).  Ignored by impls without a
    # hand-written backward.
    interaction_bwd_impl: str = "pallas"
    # atom rows per kernel tile; must match BinShape.block_n when blocking
    # metadata is consumed (data.blocking.DEFAULT_BLOCK_N)
    interaction_block_n: int = 32
    # compute precision of the hot-path kernels ("fp32" | "bf16" | "fp8"):
    # reduced precisions steer pallas-family impl names to their
    # ``pallas_<precision>`` registry variants (operand tile loads rounded,
    # fp32 accumulation — ``repro.kernels.precision``) and ride the
    # InteractionSpec into the fused kernels.  ref/fused impls have no
    # reduced-precision variant: asking for one raises at resolve time
    # rather than silently running fp32.
    precision: str = "fp32"
    dtype: Any = jnp.float32

    def __post_init__(self):
        from repro.kernels.precision import check_precision

        check_precision(self.precision)

    @property
    def hidden_spec(self) -> LSpec:
        return LSpec(self.hidden_ls)

    @property
    def a_spec(self) -> LSpec:
        return LSpec(self.a_ls)

    @property
    def sh_spec(self) -> LSpec:
        return sh_spec(self.sh_lmax)

    def h_spec_at(self, layer: int) -> LSpec:
        """Node-feature irreps entering interaction ``layer`` (first layer
        sees the scalar species embedding only)."""
        return lspec(0) if layer == 0 else self.hidden_spec

    def tp_spec_at(self, layer: int) -> TPSpec:
        return TPSpec(self.sh_spec, self.h_spec_at(layer), self.a_spec)

    def symcon_spec(self) -> SymConSpec:
        return SymConSpec(self.a_spec, self.hidden_spec, self.correlation)

    def _with_precision(self, name: str) -> str:
        """Map an impl name to its ``self.precision`` variant.

        fp32 (or the ``"auto"`` sentinel, resolved later by the build path)
        leaves the name alone; a reduced precision rewrites ``"pallas"`` to
        ``"pallas_<precision>"``, accepts a name already carrying the right
        suffix, and refuses any impl without a reduced-precision variant —
        never silently running fp32 when the config asked for less.
        """
        if self.precision == "fp32" or name == "auto":
            return name
        if name.endswith("_" + self.precision):
            return name
        if name == "pallas":
            return f"pallas_{self.precision}"
        raise ValueError(
            f"impl {name!r} has no {self.precision!r} variant; reduced "
            "precision requires the pallas kernel family "
            f"(got precision={self.precision!r})"
        )

    @property
    def symcon_impl_name(self) -> str:
        return self._with_precision(self.impl)

    @property
    def interaction_impl_name(self) -> str:
        name = self.impl if self.interaction_impl == "auto" else self.interaction_impl
        return self._with_precision(name)

    def interaction_spec_at(self, layer: int) -> InteractionSpec:
        return InteractionSpec(
            self.tp_spec_at(layer), self.avg_num_neighbors,
            self.interaction_block_n, self.interaction_bwd_impl,
            self.precision,
        )


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _linear_per_l(key, spec: LSpec, k_in: int, k_out: int, dtype) -> Params:
    keys = jax.random.split(key, len(spec.ls))
    return {
        f"l{l}_{i}": jax.random.normal(keys[i], (k_in, k_out), dtype) / np.sqrt(k_in)
        for i, l in enumerate(spec.ls)
    }


def _apply_linear_per_l(p: Params, x: jnp.ndarray, spec: LSpec) -> jnp.ndarray:
    """x: [N, k, dim(spec)] -> same-shaped with per-l channel mixing."""
    outs = []
    for i, (l, sl) in enumerate(spec.slices()):
        outs.append(jnp.einsum("nkd,kq->nqd", x[:, :, sl], p[f"l{l}_{i}"]))
    return jnp.concatenate(outs, axis=-1)


def init_mace(key: jax.Array, cfg: MaceConfig) -> Params:
    k = cfg.channels
    dt = cfg.dtype
    keys = iter(jax.random.split(key, 8 + 10 * cfg.n_interactions))
    params: Params = {
        "embed": jax.random.normal(next(keys), (cfg.n_species, k), dt)
        / np.sqrt(cfg.n_species),
        "e0": jnp.zeros((cfg.n_species,), dt),  # per-species reference energy
    }
    for t in range(cfg.n_interactions):
        h_spec = cfg.h_spec_at(t)
        tp = cfg.tp_spec_at(t)
        layer: Params = {
            "lin_up": _linear_per_l(next(keys), h_spec, k, k, dt),
            "radial": init_mlp(
                next(keys),
                (cfg.num_bessel, *cfg.radial_mlp, tp.n_paths * k),
                dt,
            ),
            "lin_a": _linear_per_l(next(keys), cfg.a_spec, k, k, dt),
            "symcon": init_symcon_weights(
                next(keys), cfg.symcon_spec(), cfg.n_species, k, dt
            ),
            "lin_msg": _linear_per_l(next(keys), cfg.hidden_spec, k, k, dt),
            # species-dependent residual ("sc" in MACE)
            "skip": {
                f"l{l}_{i}": jax.random.normal(next(keys), (cfg.n_species, k, k), dt)
                / np.sqrt(k)
                for i, l in enumerate(h_spec.ls)
                if l in cfg.hidden_spec.ls
            },
        }
        if t < cfg.n_interactions - 1:
            layer["readout"] = jax.random.normal(next(keys), (k, 1), dt) / np.sqrt(k)
        else:
            layer["readout_mlp"] = init_mlp(next(keys), (k, cfg.readout_mlp, 1), dt)
        params[f"layer_{t}"] = layer
    return params


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def mace_energy(
    params: Params,
    cfg: MaceConfig,
    species: jnp.ndarray,
    positions: jnp.ndarray,
    node_mask: jnp.ndarray,
    senders: jnp.ndarray,
    receivers: jnp.ndarray,
    edge_mask: jnp.ndarray,
    graph_id: jnp.ndarray,
    n_graphs: int,
    blocking: Optional[Dict[str, jnp.ndarray]] = None,
) -> jnp.ndarray:
    """Total potential energy per graph: [n_graphs].

    ``blocking`` is the optional pre-blocked-edge metadata from the data
    pipeline (``data.blocking.blocking_from_batch``); impls that don't
    consume it ignore it.
    """
    dt = cfg.dtype
    N = species.shape[0]
    k = cfg.channels

    with jax.named_scope("edge_geometry"):
        vec = positions[receivers] - positions[senders]      # [E, 3]
        lengths = jnp.sqrt(jnp.sum(vec * vec, axis=-1) + 1e-18)
        Y = spherical_harmonics(cfg.sh_lmax, vec).astype(dt)  # [E, dim_sh]
        radial = radial_embedding(lengths, cfg.r_max, cfg.num_bessel).astype(dt)

    # initial node features: species embedding, l=0 block
    with jax.named_scope("embedding"):
        h = params["embed"][species][:, :, None]             # [N, k, 1]
        nmask = node_mask.astype(dt)[:, None, None]
        h = h * nmask

    site_energy = jnp.zeros((N,), dt)

    for t in range(cfg.n_interactions):
        layer = params[f"layer_{t}"]
        h_spec = cfg.h_spec_at(t)
        tp_spec = cfg.tp_spec_at(t)
        # falls back to a registered TP-only impl of the same name wrapped
        # in the oracle aggregation (third-party backend extension point)
        int_fn = resolve_interaction(
            cfg.interaction_impl_name, cfg.interaction_spec_at(t)
        )
        sc_fn = resolve("symcon", cfg.symcon_impl_name, cfg.symcon_spec())

        with jax.named_scope("linear"):
            h_up = _apply_linear_per_l(layer["lin_up"], h, h_spec)
        with jax.named_scope("radial"):
            R = apply_mlp(layer["radial"], radial).reshape(-1, tp_spec.n_paths, k)
        # interaction op: TP (Algorithm 2) + masked scatter to receivers
        # + /avg_num_neighbors, fused behind one registry-resolved call
        with jax.named_scope("interaction"):
            A = int_fn(Y, h_up, R, senders, receivers, edge_mask,
                       blocking=blocking)                    # [N, k, dim_a]
        with jax.named_scope("linear"):
            A = _apply_linear_per_l(layer["lin_a"], A, cfg.a_spec)

        with jax.named_scope("symcon"):
            B = sc_fn(A, species, layer["symcon"])           # [N, k, dim_hidden]
        with jax.named_scope("linear"):
            m = _apply_linear_per_l(layer["lin_msg"], B, cfg.hidden_spec)

        # species-dependent skip (residual) from the *old* h
        with jax.named_scope("skip"):
            skip = jnp.zeros_like(m)
            for i, (l, sl_h) in enumerate(h_spec.slices()):
                if l in cfg.hidden_spec.ls:
                    W = layer["skip"][f"l{l}_{i}"][species]  # [N, k, k]
                    sl_o = cfg.hidden_spec.slice_for(l)
                    skip = skip.at[:, :, sl_o].add(
                        jnp.einsum("nkd,nkq->nqd", h[:, :, sl_h], W)
                    )
            h = (m + skip) * nmask

        with jax.named_scope("readout"):
            inv = h[:, :, cfg.hidden_spec.slice_for(0)][:, :, 0]  # [N, k]
            if t < cfg.n_interactions - 1:
                e_t = (inv @ layer["readout"])[:, 0]
            else:
                e_t = apply_mlp(layer["readout_mlp"], inv)[:, 0]
            site_energy = site_energy + e_t * node_mask.astype(dt)

    with jax.named_scope("readout"):
        site_energy = site_energy + params["e0"][species] * node_mask.astype(dt)
        return jax.ops.segment_sum(site_energy, graph_id, n_graphs)


def mace_energy_forces(
    params: Params, cfg: MaceConfig, batch: Dict[str, jnp.ndarray], n_graphs: int
):
    """Returns (energy [G], forces [N, 3]).

    Picks up the optional ``blk_*`` blocking arrays from the batch (the
    fused-interaction contract; see module docstring) when present.
    """
    from repro.data.blocking import blocking_from_batch  # deferred: layering

    blocking = blocking_from_batch(batch)

    def e_total(pos):
        e = mace_energy(
            params,
            cfg,
            batch["species"],
            pos,
            batch["node_mask"],
            batch["senders"],
            batch["receivers"],
            batch["edge_mask"],
            batch["graph_id"],
            n_graphs,
            blocking=blocking,
        )
        return jnp.sum(e), e

    grad, energy = jax.grad(e_total, has_aux=True)(batch["positions"])
    with jax.named_scope("loss"):
        forces = -grad * batch["node_mask"].astype(grad.dtype)[:, None]
    return energy, forces


def weighted_loss(
    params: Params,
    cfg: MaceConfig,
    batch: Dict[str, jnp.ndarray],
    n_graphs: int,
    energy_weight: float = 1.0,
    forces_weight: float = 100.0,
):
    """Paper §5.2's weighted (energy, forces) loss."""
    energy, forces = mace_energy_forces(params, cfg, batch, n_graphs)
    with jax.named_scope("loss"):
        nat = jnp.maximum(
            jax.ops.segment_sum(batch["node_mask"].astype(energy.dtype),
                                batch["graph_id"], n_graphs),
            1.0,
        )
        gmask = (nat > 0.5).astype(energy.dtype)
        e_err = ((energy - batch["energy"]) / nat) ** 2 * gmask
        f_err = jnp.sum(
            (forces - batch["forces"]) ** 2, axis=-1
        ) * batch["node_mask"].astype(energy.dtype)
        n_g = jnp.maximum(jnp.sum(gmask), 1.0)
        n_at = jnp.maximum(jnp.sum(batch["node_mask"].astype(energy.dtype)), 1.0)
        loss = energy_weight * jnp.sum(e_err) / n_g + forces_weight * jnp.sum(
            f_err) / (3.0 * n_at)
        return loss, {"loss": loss, "e_rmse": jnp.sqrt(jnp.sum(e_err) / n_g),
                      "f_rmse": jnp.sqrt(jnp.sum(f_err) / (3.0 * n_at))}


def param_count(params: Params) -> int:
    return sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
