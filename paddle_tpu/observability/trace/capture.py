"""Step-window trace capture: run ``jax.profiler`` around a window of
steps and correlate the XPlane artifact back into the StepTimeline.

::

    from paddle_tpu.observability import trace

    with trace.capture_steps() as cap:
        for batch in loader:
            step(*batch)          # TrainStep/fit brackets annotate
    cor = cap.result              # CorrelatedTrace
    cor.summary()["op_table"]     # top-k device-attributed ops

``StepTimeline``'s brackets are always ``trace.span``s (``pt.train.step``,
``pt.train.<phase>``), so they are in the capture; on exit the
``.xplane.pb`` is read (``xplane.correlate_logdir``), per-step device
time is ingested into ``timeline()`` (``device_compute_us`` with
``device_source="xplane"`` — every mode, not just detailed), and the
correlation digest is published to the hub's ``device_trace`` provider
(visible in ``snapshot()``/``pd_top`` and the bench telemetry dumps).

The capture window serializes nothing by itself — steps that never
synchronize may have their device tail attributed to the next window or
to ``unattributed_device_us``; loops that read the loss each step (fit
does) correlate exactly.
"""
from __future__ import annotations

import shutil
import tempfile
import threading
from typing import Any, Dict, Optional

from . import xplane

__all__ = ["StepTraceCapture", "capture_steps", "last_correlation",
           "device_trace_provider"]

_LOCK = threading.Lock()
_LAST: Optional[xplane.CorrelatedTrace] = None
_CAPTURES = 0


def last_correlation() -> Optional[xplane.CorrelatedTrace]:
    """The most recent capture's correlation (None before any capture)."""
    with _LOCK:
        return _LAST


def device_trace_provider() -> Dict[str, Any]:
    """Hub provider: the last correlation digest (one row pre-capture)."""
    with _LOCK:
        cor, n = _LAST, _CAPTURES
    if cor is None:
        return {"captures": 0}
    out = cor.summary()
    out["captures"] = n
    return out


class StepTraceCapture:
    """Context manager owning one capture window (see module docstring).

    ``logdir=None`` captures into a temp dir removed after correlation;
    pass a real dir (and ``keep_artifacts=True``) to keep the XPlane
    protobuf for TensorBoard/Perfetto/xprof.
    """

    def __init__(self, logdir: Optional[str] = None,
                 keep_artifacts: bool = False):
        self._own_dir = logdir is None
        self.logdir = logdir or tempfile.mkdtemp(prefix="pt_xplane_")
        self.keep_artifacts = keep_artifacts or not self._own_dir
        self.result: Optional[xplane.CorrelatedTrace] = None
        self.error: Optional[str] = None
        self._tracing = False

    def __enter__(self) -> "StepTraceCapture":
        import jax

        try:
            jax.profiler.start_trace(self.logdir)
            self._tracing = True
        except Exception as e:  # an already-running trace (PR-4 Profiler)
            self.error = f"start_trace failed: {e}"
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._tracing:
            import jax

            try:
                jax.profiler.stop_trace()
            except Exception as e:
                self.error = self.error or f"stop_trace failed: {e}"
            self._tracing = False
            if exc_type is None:
                self._correlate()
        if self._own_dir and not self.keep_artifacts:
            shutil.rmtree(self.logdir, ignore_errors=True)
        return False

    def _correlate(self) -> None:
        global _LAST, _CAPTURES
        try:
            cor = xplane.correlate_logdir(self.logdir)
        except Exception as e:  # telemetry never raises into the step loop
            self.error = f"correlation failed: {e}"
            return
        self.result = cor
        dev = [us for us in cor.device_us_per_step() if us > 0]
        if dev:
            from ..timeline import timeline  # it imports this package

            timeline().ingest_device_steps(dev, source="xplane")
        with _LOCK:
            _LAST = cor
            _CAPTURES += 1


def capture_steps(logdir: Optional[str] = None,
                  keep_artifacts: bool = False) -> StepTraceCapture:
    """The one-liner: ``with capture_steps() as cap: ...steps...``."""
    return StepTraceCapture(logdir=logdir, keep_artifacts=keep_artifacts)
