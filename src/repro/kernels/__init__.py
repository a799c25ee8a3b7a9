"""Optimized kernels + the dispatch registry + the autotuner.

``registry`` is the single name->implementation table for the paper's two
custom contractions (channelwise TP, symmetric contraction) and the fused
TP+scatter interaction op.  Sub-packages hold the Pallas TPU kernels;
additional backends (.cu, Triton, ...) should register themselves via
``registry.register`` with honest capability metadata (``platforms``,
``interpret_only_on``, ``has_custom_bwd``, ``consumes_blocking``,
``precision``) — the autotuner prunes candidates from exactly those flags.

**Precision / accumulation contract.**  Each kind additionally registers
``pallas_bf16`` / ``pallas_fp8`` variants (``precision`` capability
metadata): *operand tile loads* are rounded through the reduced dtype
(``precision.round_to``) while every accumulation — CG path sums, scatter
adds, the hand-written backward's cotangent reductions — stays fp32, and
every second-order pass stays fp32 at every setting.  fp8 is *emulated*
(e4m3 rounding of fp32 operands), an accuracy contract rather than a wire
format.  Grad parity vs the fp32 ref oracle is bounded per precision by
the L2 norm-relative tolerances in ``tests/test_precision.py``
(``PRECISION_TOL``); configs opt in via ``MaceConfig.precision`` which
rewrites pallas-family impl names to their variants and refuses impls
without one (never a silent fp32 run).

``autotune`` selects, per ``(kind, shape bucket, platform, mode)``, the
impl, tile geometry (``block_n``/``block_e``) and backward impl, caching
decisions in the committed ``TUNING_TABLE.json`` at the repo root:

* **Schema** (``schema`` = 1): ``{"schema", "generated_by", "entries"}``
  where each entry carries ``kind/platform/mode/bucket/dims/impl/
  block_n/block_e/bwd_impl/precision/source/score_us`` and ``source`` is
  ``"measured"`` (a ``BENCH_kernels.json`` row within the bucket distance)
  or ``"roofline"`` (the analytic model ranked the candidates).  Entries
  and trajectory rows are **precision-keyed**: lookups only consider
  same-precision entries (a bf16 row never shadows a fp32 row and vice
  versa), legacy entries/rows without the field normalise to ``"fp32"``,
  and ``build_table`` emits fp32 + bf16 rows (``TABLE_PRECISIONS``; fp8
  resolves on the fly through the roofline fallback).
* **Bucketing rule**: shape dims (N/E/k) round UP to the next power of
  two; ``nu`` matches exactly.  Queries accept the nearest entry within
  ``max |log2 ratio| <= 2`` per dim — close enough shapes share a
  decision, distant ones fall back to the roofline ranking.
* **Regeneration** (after new measurements or on new hardware)::

      PYTHONPATH=src python -m benchmarks.bench_kernels --grad [--quick]
      PYTHONPATH=src python -m repro.kernels.autotune --tune 60 --write
      PYTHONPATH=src python -m repro.kernels.autotune --check

  CI's ``tune-smoke`` runs the quick bench + ``--check`` and fails when
  the committed table is schema-invalid, incomplete, or stale against the
  fresh trajectory.

Configs opt in with the ``"auto"`` sentinel (``MaceConfig.impl`` /
``interaction_impl``); the Trainer and ``make_engine`` call
``autotune.resolve_mace_config`` at build time.  ``autotune`` is imported
lazily by its consumers (not re-exported here) to keep ``import
repro.kernels`` light.
"""
from .registry import (  # noqa: F401
    KernelImpl,
    available,
    canonical_kind,
    get_impl,
    register,
    resolve,
    unregister,
)
