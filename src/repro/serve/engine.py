"""Inference engine: one warm-compiled forward per bucket, teardown-able.

The serving twin of ``train.engine``: the same ``collate`` / compute /
``close()`` contract, minus the optimizer.  One jitted
``mace_energy_forces`` per :class:`~repro.data.collate.BinShape` bucket —
the jit cache is therefore *bounded by the ladder* and
:meth:`ServeEngine.compile_census` proves it (at most one compiled program
per bucket after :meth:`warmup`; a tail-shape retrace would show up as a
second entry).

Impl resolution mirrors ``train.engine.make_engine``: an ``"auto"``
sentinel in the :class:`MaceConfig` resolves against the committed tuning
table (``kernels.autotune``) at build time — serving computes forces as a
positions-gradient, so decisions use the honest ``fwd_bwd`` mode — and
when the selected interaction impl consumes pre-blocked edges the engine's
``collate`` emits the ``blk_*`` arrays host-side per batch, exactly like
the training pipeline.

``close()`` reuses the PR-4 teardown machinery (clear jit caches, drop
references, idempotent, context manager) so the worker fleet's
drain-and-rebuild can discard a suspect engine and build a fresh one in
the same process.
"""
from __future__ import annotations

import time
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.mace import MaceConfig, mace_energy_forces
from repro.data.collate import BinShape, collate_bin
from repro.data.molecules import Molecule
from repro.kernels import autotune
from repro.train.engine import interaction_consumes_blocking

from .buckets import bucket_key

__all__ = ["ServeEngine", "make_serve_engine", "resolve_serve_config"]


def resolve_serve_config(
    mace_cfg: MaceConfig,
    *,
    capacity: int,
    edge_factor: int,
    block_candidates: Optional[Sequence[Tuple[int, int]]] = None,
) -> Tuple[MaceConfig, Dict[str, "autotune.Decision"]]:
    """Resolve ``"auto"`` impl sentinels for the serving shape bucket.

    ``capacity`` is the *largest* bucket's node budget (the shape the hot
    path compiles for); forces are a positions-grad so the ``fwd_bwd``
    tuning rows are the honest evidence."""
    if not autotune.needs_resolution(mace_cfg):
        return mace_cfg, {}
    return autotune.resolve_mace_config(
        mace_cfg,
        capacity=capacity,
        edge_factor=edge_factor,
        mode="fwd_bwd",
        block_candidates=block_candidates,
    )


class ServeEngine:
    """Forward-only engine over a fixed bucket ladder.

    Contract (the serving half of the ``train.engine`` API):

    * ``collate(mols, bucket)``  -> (device batch, {"block_s": s})
    * ``forward(batch, bucket)`` -> (energy [G], forces [N, 3]) on device
    * ``warmup()``               -> compile every bucket once (dummy batch)
    * ``compile_census()``       -> {bucket_key: n_compiled_programs}
    * ``close()``                -> teardown (jit caches dropped); idempotent
    """

    name = "serve"

    def __init__(
        self,
        mace_cfg: MaceConfig,
        params: Any,
        buckets: Sequence[BinShape],
        *,
        strict_collate: bool = True,
    ):
        if autotune.needs_resolution(mace_cfg):
            largest = max(b.max_nodes for b in buckets)
            ef = max(b.max_edges // b.max_nodes for b in buckets)
            mace_cfg, _ = resolve_serve_config(
                mace_cfg, capacity=largest, edge_factor=ef,
                block_candidates=[(buckets[0].block_n, buckets[0].block_e)],
            )
        self.mace_cfg = mace_cfg
        self.buckets = tuple(buckets)
        self.params = jax.tree.map(jnp.asarray, params)
        self.with_blocking = interaction_consumes_blocking(mace_cfg)
        self.strict_collate = strict_collate
        if self.with_blocking:
            for b in self.buckets:
                if b.block_n != mace_cfg.interaction_block_n:
                    raise ValueError(
                        f"bucket {bucket_key(b)} block_n={b.block_n} != "
                        f"interaction_block_n={mace_cfg.interaction_block_n}"
                    )
        # one jitted forward per bucket: max_graphs is a static python int
        # baked into each closure, so each bucket owns its own jit cache and
        # the census below reads per-bucket compile counts directly
        self._fwd: Dict[str, Any] = {}
        self._bucket_by_key: Dict[str, BinShape] = {}
        for b in self.buckets:
            self._fwd[bucket_key(b)] = self._make_fwd(b)
            self._bucket_by_key[bucket_key(b)] = b

    def _make_fwd(self, bucket: BinShape):
        cfg, n_graphs = self.mace_cfg, int(bucket.max_graphs)

        @jax.jit
        def fwd(params, batch):
            return mace_energy_forces(params, cfg, batch, n_graphs)

        return fwd

    # ------------------------------ lifecycle ------------------------------

    def warmup(self) -> Dict[str, float]:
        """Compile every bucket's forward on an empty (all-padding) batch.

        Returns per-bucket compile wall seconds.  After this, steady-state
        serving never compiles: every packed bin collates to one of the
        warm shapes (partial bins are padding, not new signatures)."""
        out: Dict[str, float] = {}
        for b in self.buckets:
            t0 = time.perf_counter()
            batch, _ = self.collate([], b)
            e, f = self.forward(batch, b)
            jax.block_until_ready((e, f))
            out[bucket_key(b)] = time.perf_counter() - t0
        return out

    def close(self) -> None:
        """Teardown: clear every bucket's jit cache and drop the functions
        (PR-4 machinery — the fleet's drain-and-rebuild replaces a closed
        engine via :func:`make_serve_engine`)."""
        for fn in self._fwd.values():
            if hasattr(fn, "clear_cache"):
                fn.clear_cache()
        self._fwd = {}

    @property
    def closed(self) -> bool:
        return not self._fwd

    def __enter__(self) -> "ServeEngine":
        return self

    def __exit__(self, *exc_info) -> None:
        self.close()

    # ------------------------------- compute -------------------------------

    def collate(
        self, mols: Sequence[Molecule], bucket: BinShape
    ) -> Tuple[Dict[str, jnp.ndarray], Dict[str, float]]:
        """Host-side: pad one packed bin to its bucket's static shape (plus
        the ``blk_*`` edge blocking when the kernel consumes it).  Strict —
        serving must never drop a trailing graph on edge overflow; the
        packer's budget split guarantees fit."""
        stats = {"block_s": 0.0}
        col = collate_bin(
            mols, bucket, strict=self.strict_collate,
            with_blocking=self.with_blocking, stats=stats,
        )
        return {k: jnp.asarray(v) for k, v in col.items()}, stats

    def forward(
        self, batch: Dict[str, jnp.ndarray], bucket: BinShape
    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """(energy [max_graphs], forces [max_nodes, 3]) for one batch."""
        if self.closed:
            raise RuntimeError("serve engine is closed (rebuilt away?)")
        return self._fwd[bucket_key(bucket)](self.params, batch)

    # ------------------------------ telemetry ------------------------------

    def compile_census(self) -> Dict[str, int]:
        """Compiled-program count per bucket (jit cache sizes).

        The bucket-stability contract: after :meth:`warmup`, every entry is
        exactly 1 no matter what request mix was served — partial bins pad
        to the bucket shape instead of presenting a new leading dim.  A
        value > 1 means a retrace leaked in (the acceptance criterion
        asserted by tests and recorded in ``BENCH_serve.json``)."""
        out: Dict[str, int] = {}
        for key, fn in self._fwd.items():
            try:
                out[key] = int(fn._cache_size())
            except Exception:  # cache API moved: census degrades to -1
                out[key] = -1
        return out


def make_serve_engine(
    mace_cfg: MaceConfig,
    params: Any,
    buckets: Sequence[BinShape],
    *,
    warm: bool = True,
) -> ServeEngine:
    """Engine factory (the fleet's rebuild entry point): construct and —
    by default — warm-compile every bucket before the engine serves."""
    eng = ServeEngine(mace_cfg, params, buckets)
    if warm:
        eng.warmup()
    return eng
