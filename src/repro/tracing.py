"""Host spans and step counters on the profiler's clock.

Every host phase the training program times goes through :func:`span`: a
``jax.profiler.TraceAnnotation`` (free when no profiler runs, a host event
on the device trace's clock when one does) that also measures its own host
seconds.  The timings the program keeps (``PrefetchItem.collate_s`` and
``wait_s``, ``RankTelemetry``) are those spans' durations, so the trace and
the telemetry read one clock.

The span names are the operator's interface (each a child of the one
above it; ``data.fetch`` runs on the prefetch thread when ``prefetch >= 1``):

    train.step          StepTraceAnnotation, step_num = global step; its
                        arguments, set only while a profiler runs: the
                        ranks' real ``atoms`` as collated, and the backend
                        ``compiles`` while the step ran
      train.wait_batch    the loop blocked on the prefetch pipeline
        data.fetch          one step's host input
          data.materialise    molecules from the dataset
          data.collate        padding into the bin layout
            data.blocking       the fused interaction's edge blocking
          data.to_device      host arrays to device arrays
      engine.step
        engine.grad         dispatch of one bin's gradient (sequential)
        engine.grad_wait    wait for that gradient
        engine.stack        stacking the ranks' gradients
        engine.finalize     dispatch of the reduction and optimizer update
        engine.wait         wait for the SPMD step (shard_map, multihost)
      train.ema_update
      train.metrics       the step's metrics to host floats (waits for it)
      train.checkpoint

Device work carries ``jax.named_scope`` names in its HLO metadata instead
(``core.mace``, ``train.engine``, ``train.optimizer``).
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Any, Callable, Dict, Iterator, Optional

import jax
from jax.profiler import StepTraceAnnotation, TraceAnnotation

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Span:
    """An open span.  ``seconds`` is its host duration once it has closed;
    ``annotate`` adds arguments to its profiler event."""

    __slots__ = ("_event", "seconds")

    def __init__(self, event: TraceAnnotation):
        self._event = event
        self.seconds = 0.0

    @property
    def enabled(self) -> bool:
        """True while a profiler records host events."""
        return self._event.is_enabled()

    def annotate(self, args: Callable[[], Dict[str, Any]]) -> None:
        """Attach the arguments ``args()`` builds to the event; with no
        profiler running, ``args`` is not called."""
        if self.enabled:
            self._event.set_metadata(**args())


@contextlib.contextmanager
def span(name: str, *, step_num: Optional[int] = None) -> Iterator[Span]:
    """Open the host span ``name`` (a step span when ``step_num`` is given)
    and time it on the host clock."""
    event = (TraceAnnotation(name) if step_num is None
             else StepTraceAnnotation(name, step_num=step_num))
    s = Span(event)
    t0 = time.perf_counter()
    with event:
        try:
            yield s
        finally:
            s.seconds = time.perf_counter() - t0


class _CompileCount:
    """Backend compiles in this process, counted by one ``jax.monitoring``
    listener, registered at the first read."""

    def __init__(self):
        self._lock = threading.Lock()
        self._n = 0
        self._listening = False

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            with self._lock:
                self._n += 1

    def __call__(self) -> int:
        with self._lock:
            if not self._listening:
                jax.monitoring.register_event_duration_secs_listener(
                    self._on_event)
                self._listening = True
            return self._n


compile_count = _CompileCount()
