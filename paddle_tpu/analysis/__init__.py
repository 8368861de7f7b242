"""paddle_tpu.analysis — jaxpr-level static checking, no chip required.

The analysis half of the reference's graph-IR pass framework (SURVEY
§2.1), rebuilt TPU-native: the IR is the jaxpr jax already builds, and
every pass inspects traced programs WITHOUT running them.

    import paddle_tpu.analysis as A

    prog  = A.capture(step, x, y)          # TrainStep/callable -> op-graph
    diags = A.run_passes(prog)             # memory + spmd lints
    print(A.render(diags))

    A.retrace.enable()                     # or PT_RETRACE_AUDIT=1
    ... train ...
    print(A.render(A.retrace.report()))    # why did it recompile?

    A.selfcheck.run_selfcheck()            # repo footgun lint (CI)
    A.concurrency.run_concurrency()        # threads-and-locks lint (CC codes)

CLI: ``python tools/pd_check.py [--self | --concurrency]``. The runtime
half of the concurrency checker (``PT_LOCKDEP=1`` lock-order witness)
lives in ``A.lockdep``.
"""
from __future__ import annotations

from .diagnostics import Diagnostic, max_severity, render, to_json  # noqa: F401
from .program import OpNode, Program, capture, run_passes, PASSES  # noqa: F401
from . import memory  # noqa: F401  (registers the "memory" pass)
from . import spmd  # noqa: F401    (registers the "spmd" pass)
from . import retrace  # noqa: F401
from . import selfcheck  # noqa: F401
from . import concurrency  # noqa: F401  (CC lint: threads & locks)
from . import lockdep  # noqa: F401     (runtime lock-order witness)
from .memory import (device_hbm_bytes, PeakEstimate, estimate_peak,  # noqa: F401
                     estimate_offload_stream_hbm, estimate_train_step_hbm,
                     offload_stream_plan, stream_plan_check)
from .resilience_lint import checkpoint_story_check  # noqa: F401

__all__ = [
    "Diagnostic", "max_severity", "render", "to_json",
    "OpNode", "Program", "capture", "run_passes", "PASSES",
    "memory", "spmd", "retrace", "selfcheck", "concurrency", "lockdep",
    "device_hbm_bytes", "PeakEstimate", "estimate_peak", "estimate_train_step_hbm",
    "estimate_offload_stream_hbm", "offload_stream_plan",
    "stream_plan_check", "checkpoint_story_check",
]

# env-gated retrace audit (default off; zero overhead unless set)
retrace._maybe_enable_from_env()
