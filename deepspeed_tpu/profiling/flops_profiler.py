"""Flops profiler.

Reference: ``deepspeed/profiling/flops_profiler/profiler.py:FlopsProfiler:23``
— monkey-patches torch functions to count MACs and hooks modules for
latency, printing aggregate + per-module tables.  TPU-native redesign:

* aggregate FLOPs/bytes come from the compiled executable's
  ``cost_analysis()`` — the same HLO that runs, no estimation error;
* the per-module table comes from walking the *jaxpr*: every equation's
  FLOPs are computed analytically (dot_general/conv from shapes,
  elementwise from output size), scaled through ``scan``/``while`` trip
  counts, and attributed to the ``jax.named_scope`` name stack — the jaxpr
  is the module tree, no hooks needed.

``module_depth`` truncates the name-stack depth, ``top_modules`` limits
rows, ``detailed`` toggles the table — the reference's knobs, honored.
"""

import time
from collections import defaultdict
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from deepspeed_tpu.utils.logging import log_dist, logger


def analyze_fn_cost(fn, *args, **kwargs) -> Dict[str, float]:
    """FLOPs/bytes estimate of one jitted callable via XLA cost analysis."""
    try:
        lowered = jax.jit(fn).lower(*args, **kwargs)
        compiled = lowered.compile()
        cost = compiled.cost_analysis()
        return {
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", cost.get("bytes_accessed", 0.0))),
        }
    except Exception as e:  # cost analysis is best-effort on some backends
        logger.debug(f"cost_analysis unavailable: {e}")
        return {"flops": 0.0, "bytes_accessed": 0.0}


# --------------------------------------------------------------------------- #
# Analytic per-equation FLOP rules
# --------------------------------------------------------------------------- #
def _size(aval) -> int:
    try:
        return int(np.prod(aval.shape)) if aval.shape else 1
    except Exception:
        return 0


def _dot_general_flops(eqn) -> int:
    (lhs, rhs) = eqn.invars[0].aval, eqn.invars[1].aval
    ((lc, rc), (lb, rb)) = eqn.params["dimension_numbers"]
    contract = int(np.prod([lhs.shape[i] for i in lc])) if lc else 1
    batch = int(np.prod([lhs.shape[i] for i in lb])) if lb else 1
    m = int(np.prod([lhs.shape[i] for i in range(len(lhs.shape))
                     if i not in lc and i not in lb]))
    n = int(np.prod([rhs.shape[i] for i in range(len(rhs.shape))
                     if i not in rc and i not in rb]))
    return 2 * batch * m * n * contract


def _conv_flops(eqn) -> int:
    out = eqn.outvars[0].aval
    rhs = eqn.invars[1].aval
    return 2 * _size(out) * int(np.prod(rhs.shape[:-1])) // max(rhs.shape[-1], 1)


_ELEMENTWISE2 = {"add", "sub", "mul", "div", "max", "min", "pow", "and", "or",
                 "xor", "atan2", "rem"}
_ELEMENTWISE1 = {"exp", "log", "tanh", "logistic", "rsqrt", "sqrt", "neg",
                 "abs", "sign", "erf", "erf_inv", "sin", "cos", "floor",
                 "ceil", "round", "is_finite", "integer_pow", "cbrt", "log1p",
                 "expm1", "not"}
_REDUCE = {"reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
           "reduce_and", "reduce_or", "argmax", "argmin", "cumsum",
           "cumlogsumexp", "cummax", "cummin", "cumprod", "reduce_precision"}


def _eqn_flops(eqn) -> int:
    name = eqn.primitive.name
    if name == "dot_general":
        return _dot_general_flops(eqn)
    if name == "conv_general_dilated":
        return _conv_flops(eqn)
    if name in _ELEMENTWISE2 or name in _ELEMENTWISE1:
        return max((_size(v.aval) for v in eqn.outvars), default=0)
    if name in _REDUCE:
        return max((_size(v.aval) for v in eqn.invars), default=0)
    return 0


def _scope(eqn, prefix: str) -> str:
    stack = getattr(eqn.source_info, "name_stack", None)
    name = str(stack) if stack is not None else ""
    return "/".join(p for p in (prefix, name) if p)


def _walk(jaxpr, table: Dict[Tuple[str, str], List[int]], mult: int,
          prefix: str):
    for eqn in jaxpr.eqns:
        trips = 1
        if eqn.primitive.name == "scan":
            trips = int(eqn.params.get("length", 1))
        inner = [v for k, v in eqn.params.items()
                 if k in ("jaxpr", "call_jaxpr", "cond_jaxpr", "body_jaxpr")]
        if eqn.primitive.name == "cond":
            inner = list(eqn.params.get("branches", ()))
        for sub in inner:
            # the inner jaxpr's name stack restarts at the transform
            # boundary; carry the equation's own scope down as a prefix
            _walk(getattr(sub, "jaxpr", sub), table, mult * trips,
                  _scope(eqn, prefix))
        if not inner:
            f = _eqn_flops(eqn)
            if f:
                key = (_scope(eqn, prefix) or "<top>", eqn.primitive.name)
                table[key][0] += f * mult
                table[key][1] += mult


def jaxpr_cost_table(fn, *args, module_depth: Optional[int] = None,
                     **kwargs) -> List[Tuple[str, str, int, int]]:
    """[(scope, primitive, flops, calls)] sorted by flops desc.

    The per-module analogue of the reference's hook tables: scopes are
    ``jax.named_scope``/module names recorded in the jaxpr, primitives are
    the ops charged to them.  ``module_depth`` truncates scope paths (rows
    collapsing onto the same truncated path are merged).
    """
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    table: Dict[Tuple[str, str], List[int]] = defaultdict(lambda: [0, 0])
    _walk(closed.jaxpr, table, 1, "")
    if module_depth and module_depth > 0:
        merged: Dict[Tuple[str, str], List[int]] = defaultdict(lambda: [0, 0])
        for (scope, prim), (f, c) in table.items():
            short = "/".join(scope.split("/")[:module_depth])
            merged[(short, prim)][0] += f
            merged[(short, prim)][1] += c
        table = merged
    rows = [(scope, prim, f, c) for (scope, prim), (f, c) in table.items()]
    rows.sort(key=lambda r: -r[2])
    return rows


# --------------------------------------------------------------------------- #
class FlopsProfiler:
    """Engine-attached profiler (reference ``FlopsProfiler``; enabled by the
    ``flops_profiler`` config block and consulted at ``profile_step``)."""

    def __init__(self, engine=None, model=None):
        self.engine = engine
        self.started = False
        self.flops_per_step: Optional[float] = None
        self._t0 = None
        self.latency = 0.0
        self._tables: Dict[Any, List[Tuple[str, str, int, int]]] = {}

    def _step_fn_and_args(self, batch):
        eng = self.engine
        return (lambda p, b: eng._value_and_grad(p, b, jax.random.PRNGKey(0), 1.0),
                (eng.state.params, batch))

    def start_profile(self, batch=None, ignore_list=None, num_micro_steps: int = 1):
        if self.started:
            return
        self.started = True
        self._t0 = time.time()
        if self.engine is not None and self.flops_per_step is None and batch is not None:
            try:
                fn, args = self._step_fn_and_args(batch)
                cost = analyze_fn_cost(fn, *args)
                self.flops_per_step = cost["flops"] * num_micro_steps
                self._micro_steps = num_micro_steps
                # keep only shapes/dtypes for later re-tracing — holding the
                # device batch itself would pin a micro-batch of HBM
                self._profile_args = (fn, jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
                    if hasattr(x, "shape") else x, args))
            except Exception as e:
                logger.debug(f"flops profile failed: {e}")
                self.flops_per_step = 0.0

    def stop_profile(self):
        if not self.started:
            return
        self.latency = time.time() - (self._t0 or time.time())
        self.started = False

    def get_total_flops(self, as_string: bool = False):
        f = self.flops_per_step or 0.0
        return number_to_string(f, "FLOPs") if as_string else f

    def get_total_duration(self, as_string: bool = False):
        return duration_to_string(self.latency) if as_string else self.latency

    def module_table(self, module_depth=-1, top_modules=50):
        """Per-scope cost rows (computed lazily from the traced step;
        cached per requested depth)."""
        depth = None if module_depth in (-1, None) else module_depth
        if depth not in self._tables and getattr(self, "_profile_args", None):
            fn, args = self._profile_args
            try:
                self._tables[depth] = jaxpr_cost_table(fn, *args,
                                                       module_depth=depth)
            except Exception as e:
                logger.debug(f"jaxpr cost table failed: {e}")
                self._tables[depth] = []
        return self._tables.get(depth, [])[:top_modules]

    def breakdown_payload(self, module_depth=-1, top_modules=20):
        """Cost table as a flat JSON-ready payload — emitted once through
        the TelemetryHub as a ``flops_breakdown`` record so span timelines
        carry FLOPs attribution (tools/trace_merge.py folds it in)."""
        return {
            "flops_per_step": float(self.flops_per_step or 0.0),
            "latency_s": float(self.latency),
            "modules": [
                {"scope": scope, "op": prim, "flops": int(flops),
                 "calls": int(calls)}
                for scope, prim, flops, calls in self.module_table(
                    module_depth=module_depth, top_modules=top_modules)
            ],
        }

    def print_model_profile(self, profile_step=1, module_depth=-1, top_modules=3,
                            detailed=True, output_file=None):
        lines = [f"flops per step: {self.get_total_flops(True)}, "
                 f"latency: {self.get_total_duration(True)}"]
        if self.latency > 0 and self.flops_per_step:
            lines[0] += (f", achieved: "
                         f"{number_to_string(self.flops_per_step / self.latency, 'FLOPS')}")
        if detailed:
            rows = self.module_table(module_depth=module_depth,
                                     top_modules=max(top_modules, 1))
            if rows:
                width = max(len(r[0]) for r in rows)
                lines.append(f"{'module':<{width}}  {'op':<20} {'GFLOPs':>10} {'calls':>8}")
                for scope, prim, flops, calls in rows:
                    lines.append(f"{scope:<{width}}  {prim:<20} "
                                 f"{flops / 1e9:>10.3f} {calls:>8}")
        msg = "\n".join(lines)
        if output_file:
            with open(output_file, "w") as f:
                f.write(msg + "\n")
        log_dist(msg, ranks=[0])

    def end_profile(self):
        self.stop_profile()


def number_to_string(num, units=None, precision=2):
    if units is None:
        units = ""
    for scale, suffix in [(1e12, "T"), (1e9, "G"), (1e6, "M"), (1e3, "K")]:
        if abs(num) >= scale:
            return f"{num / scale:.{precision}f} {suffix}{units}"
    return f"{num:.{precision}f} {units}"


def duration_to_string(seconds, precision=2):
    if seconds >= 1:
        return f"{seconds:.{precision}f} s"
    if seconds >= 1e-3:
        return f"{seconds * 1e3:.{precision}f} ms"
    return f"{seconds * 1e6:.{precision}f} us"
