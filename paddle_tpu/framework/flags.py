"""Global flag registry: the gflags tier of the reference's config system.

Reference: paddle/fluid/platform/flags.cc (49 PADDLE_DEFINE_EXPORTED_* flags)
surfaced to Python via pybind/global_value_getter_setter.cc and settable by
``FLAGS_*`` env vars or ``paddle.set_flags``.

TPU-native design: flags are plain typed Python values in a process-global
registry. Env vars named ``FLAGS_<name>`` override the default at first import
(same contract as the reference's gflags env pickup). A handful of flags are
*live*: consumers read them per call (e.g. ``FLAGS_check_nan_inf`` is read by
core.dispatch on every op), so ``set_flags`` takes effect immediately without
re-tracing.
"""
from __future__ import annotations

import os
from typing import Any, Dict, Iterable, Union

_DEFS: Dict[str, dict] = {}
_VALUES: Dict[str, Any] = {}


def _parse(raw: str, typ):
    if typ is bool:
        return raw.lower() in ("1", "true", "yes", "on")
    return typ(raw)


def define_flag(name: str, default, doc: str = ""):
    """Register a flag (PADDLE_DEFINE_EXPORTED_* equivalent, flags.cc)."""
    typ = type(default)
    _DEFS[name] = {"default": default, "type": typ, "doc": doc}
    env = os.environ.get(f"FLAGS_{name}")
    _VALUES[name] = _parse(env, typ) if env is not None else default
    return name


_ON_SET: Dict[str, Any] = {}


def on_set(name: str, hook):
    """Register a side-effect hook fired when `name` is set (the role of
    the reference's flag callbacks in global_value_getter_setter.cc)."""
    _ON_SET[name] = hook


def set_flags(flags: Dict[str, Any]):
    """paddle.set_flags: update registered flags (global_value_getter_setter.cc)."""
    for k, v in flags.items():
        name = k[6:] if k.startswith("FLAGS_") else k
        if name not in _DEFS:
            raise ValueError(f"unknown flag {k!r}; known: {sorted(_DEFS)}")
        val = _parse(v, _DEFS[name]["type"]) if isinstance(v, str) \
            else _DEFS[name]["type"](v)
        if name in _ON_SET:
            _ON_SET[name](val)  # hooks validate BEFORE the value is stored
        _VALUES[name] = val


def get_flags(flags: Union[str, Iterable[str], None] = None) -> Dict[str, Any]:
    """paddle.get_flags: read one, several, or all flags."""
    if flags is None:
        return {f"FLAGS_{k}": v for k, v in _VALUES.items()}
    if isinstance(flags, str):
        flags = [flags]
    out = {}
    for k in flags:
        name = k[6:] if k.startswith("FLAGS_") else k
        if name not in _DEFS:
            raise ValueError(f"unknown flag {k!r}")
        out[f"FLAGS_{name}"] = _VALUES[name]
    return out


def flag(name: str):
    """Fast internal read for hot paths."""
    return _VALUES[name]


# -- the registry (TPU-relevant subset of flags.cc, same semantics) -----------
define_flag("check_nan_inf", False,
            "Assert every op's outputs are finite; raises naming the op "
            "(reference: framework/details/nan_inf_utils_detail.*).")
define_flag("check_nan_inf_action", "raise",
            "What a check_nan_inf trip does: 'raise' (default) aborts the "
            "step naming the op; 'log' downgrades to a warning + a "
            "nan_inf_events counter row so monitors can alert without "
            "crashing the run; 'skip' raises NanStepSkipped, which "
            "step-aware loops (hapi.Model.fit) eat — the poisoned step is "
            "dropped (grads cleared, no update) and training continues, "
            "counted as resilience skipped_steps. Either way the trip is "
            "counted.")
define_flag("benchmark", False,
            "Block on every op so host timings are true device timings "
            "(reference: flags.cc FLAGS_benchmark).")
define_flag("low_precision_op_list", False,
            "Record which ops ran in bf16 under AMP.")
define_flag("use_pallas_flash_attention", True,
            "Route nn.functional attention through the Pallas flash kernel.")
define_flag("allocator_strategy", "auto_growth",
            "Kept for API parity; XLA/PJRT owns device memory on TPU.")
define_flag("fraction_of_gpu_memory_to_use", 0.92,
            "Kept for API parity; maps to XLA_PYTHON_CLIENT_MEM_FRACTION.")
define_flag("cudnn_deterministic", False,
            "Determinism toggle; maps to XLA deterministic-ops mode.")
define_flag("max_inplace_grad_add", 0,
            "Kept for API parity with the reference's grad-accumulation flag.")
define_flag("call_stack_level", 1,
            "Error-report verbosity (reference: enforce.h FLAGS_call_stack_level).")
define_flag("profiler_host_spans", True,
            "Record host-side RecordEvent spans while a Profiler is active.")
define_flag("flash_block_q", 0,
            "flash-attention q block size (0 = kernel default 256)")
define_flag("flash_block_k", 0,
            "flash-attention k block size (0 = kernel default 512)")
define_flag("flash_bwd_block_q", 0,
            "flash-attention BACKWARD q block size (0 = same as forward); "
            "the bwd kernels hold more f32 VMEM operands so smaller blocks "
            "can pipeline better; on a TPU a multiple of 128 or the whole "
            "sequence (lse and delta are read in row blocks)")
define_flag("flash_bwd_block_k", 0,
            "flash-attention BACKWARD k block size (0 = same as forward)")
define_flag("remat_policy", "",
            "recompute policy for scanned stacks. Every policy keeps what a "
            "row-parallel layer all-reduced over mp and the flash-attention "
            "kernel's o+lse (its backward's residuals: the replayed layer "
            "never runs the forward kernel again; 2 x batch x seq x hidden "
            "bytes + an lse a layer). ''=what fits: the layer's named "
            "projection outputs (q/k/v, o, up, gate) as far as the compiled "
            "step's memory_analysis() leaves room under the device's "
            "bytes_limit (jit/remat_fit.py; chosen at the step's first call "
            "and remembered beside the compile cache), nothing more where "
            "the device states no limit (CPU); 'flash'=that minimum on "
            "every device, no ladder; 'dots'=save "
            "non-batch matmul outputs, 'dots_all'=save all matmul outputs, "
            "'moe'=also pin "
            "the MoE capacity buffer/expert outputs/routing maps, "
            "'route'=pin only the MoE "
            "routing decisions (~1MB/layer); 'moe'/'route' names exist "
            "only on the default index dispatch path")
define_flag("moe_dispatch", "index",
            "MoE token dispatch: 'index' (cumsum capacity routing, default), "
            "'sort' (argsort capacity routing), 'gmm' (dropless grouped "
            "matmul, single-device experts), 'fused' (dropless Pallas "
            "routing/dispatch kernel feeding the grouped matmul, "
            "single-device experts — kernels/pallas/moe_dispatch.py) or "
            "'einsum' (GShard one-hot dispatch einsums, oracle)")
define_flag("flash_min_seq", 128,
            "Minimum q AND kv sequence length before nn.functional "
            "attention routes to the Pallas flash kernel on TPU (shorter "
            "sequences stay on the fused-XLA softmax path, where the "
            "kernel's block pipeline has nothing to hide). The chosen "
            "path is a primitive attr, so the analysis.retrace auditor "
            "names any threshold-driven flip.")
define_flag("embedding_oov_policy", "error",
            "F.embedding out-of-vocabulary id policy: 'error' (default) "
            "raises on concrete eager ids outside [0, num_rows) — inside "
            "a traced program ids are abstract and keep XLA's clamped "
            "gather, documented; 'clip' opts into the silent clamp "
            "everywhere (the pre-PR-14 jnp.take behavior). Per-call "
            "override via F.embedding(..., oov_policy=).")
define_flag("sparse_embedding_min_rows", 16384,
            "nn.Embedding(sparse=True) routes to the host-sharded "
            "ShardedEmbeddingTable (dedup lookup, hot-row device cache, "
            "sparse row grads) only at/above this row count; smaller "
            "tables keep the dense device parameter — the documented "
            "dense fallback (a table that fits HBM gains nothing from "
            "host residency, and dense grads keep it inside compiled "
            "train steps).")
define_flag("matmul_precision", "default",
            "XLA matmul/conv precision: 'default' (bf16 mantissas on the "
            "MXU), 'high', or 'highest' (full f32 — use for parity "
            "comparisons against CPU references)")


def _apply_matmul_precision(value: str):
    import jax

    if value not in ("default", "high", "highest"):
        raise ValueError(
            f"FLAGS_matmul_precision must be default/high/highest, "
            f"got {value!r}")
    jax.config.update("jax_default_matmul_precision",
                      None if value == "default" else value)


def _validate_nan_inf_action(value: str):
    if value not in ("raise", "log", "skip"):
        raise ValueError(
            f"FLAGS_check_nan_inf_action must be 'raise', 'log' or 'skip', "
            f"got {value!r}")


on_set("check_nan_inf_action", _validate_nan_inf_action)
on_set("matmul_precision", _apply_matmul_precision)
# env-var initialization fires the hooks too (define_flag only stores)
if _VALUES.get("matmul_precision", "default") != "default":
    _apply_matmul_precision(_VALUES["matmul_precision"])
if _VALUES.get("check_nan_inf_action", "raise") != "raise":
    _validate_nan_inf_action(_VALUES["check_nan_inf_action"])
