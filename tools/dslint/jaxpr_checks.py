"""jaxpr pass — structural checks on the traced step programs.

The AST passes read what the source *says*; this pass reads what the
compiler *gets*.  It builds the three tentpole step programs on an
8-virtual-device CPU mesh — the layered ZeRO-3 training step, the bulk
explicit-collective step, and the paged serving step — traces
each to a jaxpr with :func:`jax.make_jaxpr` (no compilation, no
execution), and asserts two structural properties:

1. **No host round-trips**: no ``pure_callback`` / ``io_callback`` /
   ``debug_callback`` / infeed-outfeed / ``device_put`` equation
   anywhere in the program, including every sub-jaxpr (scan bodies,
   cond branches, custom-vjp rules).  A stray callback turns "zero-sync
   step" into a per-step device drain that no numeric test notices.

2. **Identical collective issue order across shard roles**.  The repo
   runs single-controller SPMD: every shard executes the one traced
   program, so collective order can only diverge through
   (a) a ``cond`` whose branches carry different collective sequences
   (shards taking different branches then issue mismatched collectives
   and deadlock cross-rank), or (b) a data-dependent ``while`` whose
   body issues collectives (shards may loop different trip counts).
   The pass extracts the collective sequence recursively, requires every
   ``cond``'s branches to agree, and forbids collectives inside
   ``while`` bodies; an unconditional program order plus those two rules
   *is* the cross-shard ordering proof.

The per-program reports (collective sequence, equation counts) land in
``ctx.meta["jaxpr"]`` and are emitted by ``--json``.

jax import discipline: device count is fixed at first jax import.  When
this module runs from the ``tools.dslint`` CLI, ``__main__`` has already
forced ``JAX_PLATFORMS=cpu`` with 8 virtual devices *before* importing
jax.  When jax was imported earlier with fewer devices (e.g. a REPL),
the pass re-execs itself in a subprocess with the right environment
instead of silently tracing a 1-device mesh.
"""

import json
import os
import subprocess
import sys
from typing import Dict, List, Optional, Tuple

from tools.dslint.core import Context, Finding, LintPass

PASS_NAME = "jaxpr"

REQUIRED_DEVICES = 8

#: primitives that round-trip through the host inside a step program
CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "outside_call", "host_callback_call",
})
TRANSFER_PRIMS = frozenset({"device_put", "infeed", "outfeed"})

#: cross-device collective primitives whose issue order must match on
#: every shard (a mismatched order is a cross-rank deadlock)
COLLECTIVE_PRIMS = frozenset({
    "psum", "pmax", "pmin", "ppermute", "pshuffle", "all_gather",
    "all_to_all", "reduce_scatter", "psum_scatter", "pdot", "pgather",
})

_HINT_CALLBACK = ("host callbacks inside a step program force a per-step "
                  "device drain; move the host work to the telemetry "
                  "windowed drain")
_HINT_DIVERGE = ("shards taking different branches would issue mismatched "
                 "collective sequences and deadlock cross-rank; hoist the "
                 "collective out of the cond (or make both branches issue "
                 "the identical sequence)")
_HINT_WHILE = ("a data-dependent while can run different trip counts on "
               "different shards; collectives inside its body deadlock "
               "cross-rank — restructure as a static-length scan")


def _sub_jaxprs(params: Dict):
    """Every (Closed)Jaxpr reachable from an eqn's params, in order.
    Duck-typed (``.eqns`` present = Jaxpr, ``.jaxpr.eqns`` = ClosedJaxpr)
    so it never imports jax machinery per call."""
    def _walk(v):
        if hasattr(v, "eqns") and hasattr(v, "invars"):       # raw Jaxpr
            yield v
        elif hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):  # ClosedJaxpr
            yield v.jaxpr
        elif isinstance(v, (tuple, list)):
            for item in v:
                yield from _walk(item)

    for v in params.values():
        yield from _walk(v)


def iter_all_eqns(jaxpr):
    """Depth-first over every equation, descending into all sub-jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn.params):
            yield from iter_all_eqns(sub)


def _collective_desc(eqn) -> str:
    axes = eqn.params.get("axis_name", eqn.params.get("axes"))
    return (f"{eqn.primitive.name}[{axes}]" if axes is not None
            else eqn.primitive.name)


def collective_sequence(jaxpr, program: str,
                        findings: List[Finding]) -> List[str]:
    """The program-order collective sequence; appends a finding for every
    construct under which the sequence could differ between shards."""
    seq: List[str] = []
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim in COLLECTIVE_PRIMS:
            seq.append(_collective_desc(eqn))
            continue
        if prim == "cond":
            branch_seqs = [collective_sequence(b.jaxpr, program, findings)
                           for b in eqn.params["branches"]]
            if any(s != branch_seqs[0] for s in branch_seqs[1:]):
                findings.append(Finding(
                    PASS_NAME, f"jaxpr://{program}", 0,
                    f"cond branches issue different collective sequences: "
                    f"{branch_seqs}", hint=_HINT_DIVERGE))
            seq.extend(branch_seqs[0])
            continue
        if prim == "while":
            body = eqn.params["body_jaxpr"].jaxpr
            body_seq = collective_sequence(body, program, findings)
            if body_seq:
                findings.append(Finding(
                    PASS_NAME, f"jaxpr://{program}", 0,
                    f"collectives {body_seq} inside a data-dependent "
                    f"while body", hint=_HINT_WHILE))
            # cond_jaxpr collectives would diverge the trip decision too
            cond_seq = collective_sequence(eqn.params["cond_jaxpr"].jaxpr,
                                           program, findings)
            seq.extend(cond_seq)
            continue
        if prim == "scan":
            inner = collective_sequence(eqn.params["jaxpr"].jaxpr,
                                        program, findings)
            if inner:
                # static trip count: the same sequence on every shard,
                # repeated length times — record it symbolically
                seq.append(f"scan[{eqn.params.get('length')}x{inner}]")
            continue
        for sub in _sub_jaxprs(eqn.params):
            seq.extend(collective_sequence(sub, program, findings))
    return seq


def analyze_jaxpr(closed_jaxpr, program: str = "program"
                  ) -> Tuple[List[Finding], Dict]:
    """Run both structural checks on one traced program.

    Returns ``(findings, report)``; the report carries the collective
    sequence and equation counts for ``--json`` consumers and tests.
    """
    findings: List[Finding] = []
    jaxpr = closed_jaxpr.jaxpr
    n_eqns = 0
    for eqn in iter_all_eqns(jaxpr):
        n_eqns += 1
        prim = eqn.primitive.name
        if prim in CALLBACK_PRIMS:
            findings.append(Finding(
                PASS_NAME, f"jaxpr://{program}", 0,
                f"host callback primitive {prim} in the traced program",
                hint=_HINT_CALLBACK))
        elif prim in TRANSFER_PRIMS:
            findings.append(Finding(
                PASS_NAME, f"jaxpr://{program}", 0,
                f"host-transfer primitive {prim} in the traced program",
                hint=_HINT_CALLBACK))
    collectives = collective_sequence(jaxpr, program, findings)
    report = {"eqns": n_eqns, "collectives": collectives,
              "num_collectives": len(collectives),
              "clean": not findings}
    return findings, report


# --------------------------------------------------------------------------- #
# program builders — tiny models, trace-only (never compiled or run)
# --------------------------------------------------------------------------- #

_TRAIN_CFG = dict(vocab_size=128, n_positions=32, n_embd=64, n_layer=4,
                  n_head=4, attn_impl="reference")


def _make_train_engine(**zero_over):
    import jax
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt import GPT, GPTConfig
    import jax.numpy as jnp
    model = GPT(GPTConfig(dtype=jnp.float32, **_TRAIN_CFG))
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=model.init_params(jax.random.key(0)),
        config={"train_batch_size": 8,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 3, **zero_over}},
        seed=7)
    return engine


def trace_programs() -> Dict[str, object]:
    """name -> ClosedJaxpr for the three tentpole step programs."""
    import numpy as np
    import jax

    out: Dict[str, object] = {}
    ids = np.arange(8 * 32, dtype=np.int32).reshape(8, 32) % 128

    # -- layered ZeRO-3 training step ----------------------------------- #
    eng = _make_train_engine(overlap_comm=True)
    assert eng._layered_active(), (
        "layered step unavailable on this mesh — the structural check "
        "would be vacuous")
    batch = eng._place_batch((ids, ids))
    step = eng._build_layered_step(batch)
    out["layered-step"] = jax.make_jaxpr(step)(
        eng.state.params, batch, eng._next_rng(), eng.state.scaler.scale)

    # -- bulk explicit-collective step ---------------------------------- #
    eng_b = _make_train_engine(zero_quantized_weights=True)
    batch_b = eng_b._place_batch((ids, ids))
    step_b = eng_b._build_cc_step(batch_b)
    out["bulk-step"] = jax.make_jaxpr(step_b)(
        eng_b.state.params, batch_b, eng_b._next_rng(),
        eng_b.state.scaler.scale)

    # -- paged serving step (decode rows + prompt chunk) ------------------ #
    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt import GPT, GPTConfig
    from deepspeed_tpu.serving import DeepSpeedServingConfig, ServingEngine
    smodel = GPT(GPTConfig(vocab_size=128, n_positions=128, n_embd=32,
                           n_layer=2, n_head=4, dtype="float32"))
    srv = ServingEngine(
        smodel, DeepSpeedServingConfig(block_size=8, num_blocks=128,
                                       max_batch_size=8, prefill_chunk=16,
                                       dtype="float32"), seed=0)
    # the step's one upload (8 slots + the chunk's 16 rows, the tables'
    # edits) and the token array of the program before, beside its state:
    # the arena and the slots' tables
    out["serving-step"] = jax.make_jaxpr(srv._step_fn)(
        srv.params, jnp.zeros((srv._layout.packed_size,), jnp.int32),
        srv._previous, srv._k_pages, srv._v_pages, srv._tables)
    return out


# --------------------------------------------------------------------------- #
# the pass
# --------------------------------------------------------------------------- #

_SUBPROC_GUARD = "DSLINT_JAXPR_SUBPROCESS"


def _run_in_subprocess(repo_root: str) -> Tuple[List[Finding], Dict]:
    """jax is already imported with the wrong device count — re-exec the
    jaxpr pass alone under a fresh interpreter with 8 CPU devices."""
    env = dict(os.environ)
    env[_SUBPROC_GUARD] = "1"
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count="
                        f"{REQUIRED_DEVICES}").strip()
    proc = subprocess.run(
        [sys.executable, "-m", "tools.dslint", "--only", PASS_NAME,
         "--json"],
        cwd=repo_root, env=env, capture_output=True, text=True, timeout=900)
    if proc.returncode not in (0, 1):
        return [Finding(PASS_NAME, "jaxpr://subprocess", 0,
                        f"jaxpr subprocess failed (rc={proc.returncode}): "
                        f"{proc.stderr.strip()[-500:]}")], {}
    report = json.loads(proc.stdout)
    findings = [Finding(f["pass"], f["file"], f["line"], f["message"],
                        hint=f.get("hint", ""),
                        severity=f.get("severity", "error"))
                for f in report.get("findings", [])]
    return findings, report.get("meta", {}).get("jaxpr", {})


class JaxprPass(LintPass):
    name = PASS_NAME
    description = ("trace the layered/bulk/serving step programs on an "
                   "8-device CPU mesh; assert zero host callbacks and "
                   "shard-invariant collective issue order")

    def run(self, ctx: Context) -> List[Finding]:
        already = "jax" in sys.modules
        if not already:
            os.environ.setdefault("JAX_PLATFORMS", "cpu")
            flags = os.environ.get("XLA_FLAGS", "")
            if "xla_force_host_platform_device_count" not in flags:
                os.environ["XLA_FLAGS"] = (
                    flags + " --xla_force_host_platform_device_count="
                    f"{REQUIRED_DEVICES}").strip()
        import jax
        if jax.device_count() < REQUIRED_DEVICES:
            if os.environ.get(_SUBPROC_GUARD):
                return [Finding(
                    PASS_NAME, "jaxpr://environment", 0,
                    f"only {jax.device_count()} device(s) even in the "
                    f"re-exec subprocess — cannot form the "
                    f"{REQUIRED_DEVICES}-shard mesh")]
            findings, meta = _run_in_subprocess(ctx.repo_root)
            ctx.meta["jaxpr"] = meta
            return findings

        # engine construction logs to stdout (the handler binds the stream
        # at first deepspeed_tpu import) — route it to stderr so --json
        # stdout stays a single parseable document
        from contextlib import redirect_stdout
        with redirect_stdout(sys.stderr):
            programs = trace_programs()
        findings: List[Finding] = []
        reports: Dict[str, Dict] = {}
        for program, closed in programs.items():
            fs, report = analyze_jaxpr(closed, program=program)
            findings.extend(fs)
            reports[program] = report
        ctx.meta["jaxpr"] = reports
        return findings
