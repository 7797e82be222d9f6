"""tools/dslint end to end: the repo-clean tier-1 gate, one seeded
violation fixture per pass, the CLI contract (exit codes, --json), and
the regression test for the offload-store race the lock-discipline
triage surfaced."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO_ROOT)

from tools.dslint import core  # noqa: E402
from tools.dslint import (jaxpr_checks, lock_discipline, monotonic,  # noqa: E402
                          overlap, pallas_discipline, stale_pragma, zero_sync)


def _scan(tmp_path, src, name="fixture.py", ctx=None):
    p = tmp_path / name
    p.write_text(src)
    ctx = ctx or core.Context()
    return ctx.scan(str(p)), ctx


# --------------------------------------------------------------------------- #
# the gate: the repo itself must be clean
# --------------------------------------------------------------------------- #

class TestRepoClean:
    def test_source_passes_clean_on_repo(self):
        """Every AST pass over the committed tree: zero findings.  (The
        jaxpr pass is exercised through the CLI test below — one trace.)"""
        findings, ctx = core.run_passes(only=[
            "zero-sync", "lock-discipline", "monotonic", "overlap",
            "pallas-discipline", "stale-pragma"])
        assert findings == [], "\n".join(f.format() for f in findings)
        assert ctx.ran == ["zero-sync", "lock-discipline", "monotonic",
                           "overlap", "pallas-discipline", "stale-pragma"]

    def test_cli_full_run_clean_with_jaxpr_proof(self):
        """``python -m tools.dslint --json`` exits 0 on the repo, and the
        jaxpr report proves the acceptance property: the layered stage-3
        step traced on the 8-device CPU mesh has zero host callbacks and
        a shard-invariant collective issue order (no divergent cond /
        no collective under a data-dependent while)."""
        proc = subprocess.run(
            [sys.executable, "-m", "tools.dslint", "--json"],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=600)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        report = json.loads(proc.stdout)
        assert report["clean"] is True
        assert report["passes_run"] == ["zero-sync", "lock-discipline",
                                        "monotonic", "overlap",
                                        "pallas-discipline", "jaxpr",
                                        "stale-pragma"]
        jx = report["meta"]["jaxpr"]
        for program in ("layered-step", "bulk-step", "serving-step"):
            assert jx[program]["clean"] is True, jx[program]
        # the layered step really contains collectives (the check is not
        # vacuous), and their extracted order is the cross-shard proof
        assert jx["layered-step"]["num_collectives"] > 0
        assert jx["bulk-step"]["num_collectives"] > 0

    def test_cli_unknown_pass_is_usage_error(self):
        proc = subprocess.run(
            [sys.executable, "-m", "tools.dslint", "--only", "bogus"],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=60)
        assert proc.returncode == 2
        assert "unknown pass" in proc.stderr


# --------------------------------------------------------------------------- #
# seeded violations: each pass must catch its fixture
# --------------------------------------------------------------------------- #

class TestZeroSyncPass:
    def test_catches_each_sync_pattern(self, tmp_path):
        sf, _ = _scan(tmp_path, (
            "import numpy as np\n"
            "import jax\n"
            "def record_step(x, y):\n"
            "    a = x.item()\n"
            "    b = float(y)\n"
            "    c = np.asarray(x)\n"
            "    d = jax.device_get(y)\n"
            "    x.block_until_ready()\n"
            "    return a, b, c, d\n"))
        msgs = [m for _, m in zero_sync.scope_violations(sf, "record_step")]
        assert len(msgs) == 5
        for needle in (".item()", "float()", "np.asarray()", "device_get",
                       "block_until_ready"):
            assert any(needle in m for m in msgs), (needle, msgs)

    def test_constant_coercion_and_out_of_scope_ignored(self, tmp_path):
        sf, _ = _scan(tmp_path, (
            "def record_step(x):\n"
            "    return int(3)\n"        # constant: not a sync
            "def elsewhere(x):\n"
            "    return x.item()\n"))    # outside the checked scope
        assert list(zero_sync.scope_violations(sf, "record_step")) == []

    def test_missing_scope_is_a_violation(self, tmp_path):
        sf, _ = _scan(tmp_path, "def other():\n    pass\n")
        msgs = [m for _, m in zero_sync.scope_violations(sf, "record_step")]
        assert msgs == ["guarded function record_step() not found"]

    def test_pragma_sanctions_the_line(self, tmp_path):
        p = tmp_path / "ok.py"
        p.write_text("def record_step(step):\n"
                     "    # dslint: ok(zero-sync) - host counter\n"
                     "    return int(step)\n")
        ctx = core.Context()
        sf = ctx.scan(str(p), for_pass="zero-sync")
        out = [(ln, m) for ln, m in zero_sync.scope_violations(
                   sf, "record_step")
               if not ctx.sanctioned(sf, ln, "zero-sync")]
        assert out == []

    def test_metrics_hot_path_scopes_are_guarded(self):
        """The live metrics plane's inc/set/observe and the SLO
        monitor's evaluate are in the checked-scope roster."""
        scopes = set(zero_sync.CHECKED_SCOPES)
        for scope in ("inc", "set", "observe"):
            assert ("deepspeed_tpu/telemetry/metrics.py", scope) in scopes
        assert ("deepspeed_tpu/telemetry/slo.py", "evaluate") in scopes

    def test_ledger_hot_path_scopes_are_guarded(self):
        """The goodput ledger's per-step attribution (on_step) and its
        registry mirror (_acc) are in the checked-scope roster."""
        scopes = set(zero_sync.CHECKED_SCOPES)
        for scope in ("on_step", "_acc"):
            assert ("deepspeed_tpu/telemetry/ledger.py", scope) in scopes

    def test_seeded_sync_in_ledger_hot_path_is_flagged(self, tmp_path):
        """A seeded violation in an on_step-style attribution method —
        coercing a possibly-traced loss to book a category — is caught."""
        sf, _ = _scan(tmp_path, (
            "class Ledger:\n"
            "    def on_step(self, step, loss):\n"
            "        span = float(loss)\n"
            "        self._cats['productive'] += span.item()\n"))
        msgs = [m for _, m in zero_sync.scope_violations(sf, "on_step")]
        assert len(msgs) == 2
        assert any("float()" in m for m in msgs)
        assert any(".item()" in m for m in msgs)

    def test_live_ledger_hot_path_is_clean(self):
        """The real ledger.py on_step/_acc pass the zero-sync check with
        no pragmas — the hot path stays coercion-free by construction."""
        ctx = core.Context()
        sf = ctx.scan("deepspeed_tpu/telemetry/ledger.py",
                      for_pass="zero-sync")
        for scope in ("on_step", "_acc"):
            assert list(zero_sync.scope_violations(sf, scope)) == []

    def test_seeded_sync_in_metrics_hot_path_is_flagged(self, tmp_path):
        """A seeded violation in a registry-style observe() — somebody
        handing a device value straight to a histogram — is caught."""
        sf, _ = _scan(tmp_path, (
            "class Histogram:\n"
            "    def observe(self, value):\n"
            "        v = float(value)\n"
            "        self._sum += v.item()\n"))
        msgs = [m for _, m in zero_sync.scope_violations(sf, "observe")]
        assert len(msgs) == 2
        assert any("float()" in m for m in msgs)
        assert any(".item()" in m for m in msgs)

    def test_collective_hot_path_scopes_are_guarded(self):
        """The collective health plane's staged hot path — the comm
        facade's _log_op and the monitor's begin/end/fingerprint — is in
        the checked-scope roster."""
        scopes = set(zero_sync.CHECKED_SCOPES)
        assert ("deepspeed_tpu/comm/comm.py", "_log_op") in scopes
        for scope in ("begin", "end", "fingerprint_of"):
            assert ("deepspeed_tpu/telemetry/collective_monitor.py",
                    scope) in scopes

    def test_seeded_sync_in_collective_hot_path_is_flagged(self, tmp_path):
        """A seeded violation in a monitor-style begin() — coercing the
        traced tensor's shape/value to build the record — is caught."""
        sf, _ = _scan(tmp_path, (
            "class Monitor:\n"
            "    def begin(self, op, tensor):\n"
            "        shape = tuple(int(d) for d in tensor.shape)\n"
            "        nbytes = float(tensor.nbytes)\n"
            "        return {'op': op, 'shape': shape, 'bytes': nbytes}\n"))
        msgs = [m for _, m in zero_sync.scope_violations(sf, "begin")]
        assert len(msgs) == 2
        assert any("int()" in m for m in msgs)
        assert any("float()" in m for m in msgs)

    def test_live_collective_hot_path_is_clean(self):
        """The real comm._log_op and collective_monitor begin/end/
        fingerprint_of pass the zero-sync check with no pragmas — records
        carry raw trace-time metadata; int-ification happens at view
        time, outside the hot path."""
        ctx = core.Context()
        sf = ctx.scan("deepspeed_tpu/comm/comm.py", for_pass="zero-sync")
        assert list(zero_sync.scope_violations(sf, "_log_op")) == []
        sf = ctx.scan("deepspeed_tpu/telemetry/collective_monitor.py",
                      for_pass="zero-sync")
        for scope in ("begin", "end", "fingerprint_of"):
            assert list(zero_sync.scope_violations(sf, scope)) == []

    def test_serving_resilience_hot_path_scopes_are_guarded(self):
        """The admission ladder, deadline scan and queue-age probe run at
        every serving step boundary — all in the checked-scope roster."""
        scopes = set(zero_sync.CHECKED_SCOPES)
        for scope in ("evaluate", "admit_ok", "cap_new_tokens", "expired",
                      "oldest_wait_s"):
            assert ("deepspeed_tpu/serving/scheduler.py", scope) in scopes
        for scope in ("_expire_deadlines", "_update_admission"):
            assert ("deepspeed_tpu/serving/engine.py", scope) in scopes

    def test_seeded_sync_in_admission_hot_path_is_flagged(self, tmp_path):
        """A seeded violation in an evaluate()-style ladder step —
        coercing a device-resident queue gauge into the age signal — is
        caught."""
        sf, _ = _scan(tmp_path, (
            "class Admission:\n"
            "    def evaluate(self, queue_age_gauge, state):\n"
            "        age = float(queue_age_gauge)\n"
            "        depth = queue_age_gauge.item()\n"
            "        return age + depth\n"))
        msgs = [m for _, m in zero_sync.scope_violations(sf, "evaluate")]
        assert len(msgs) == 2
        assert any("float()" in m for m in msgs)
        assert any(".item()" in m for m in msgs)

    def test_live_serving_resilience_hot_path_is_clean(self):
        """The real scheduler/engine resilience scopes pass with no
        pragmas — config coercions were hoisted to construction time."""
        ctx = core.Context()
        sf = ctx.scan("deepspeed_tpu/serving/scheduler.py",
                      for_pass="zero-sync")
        for scope in ("evaluate", "admit_ok", "cap_new_tokens", "expired",
                      "oldest_wait_s"):
            assert list(zero_sync.scope_violations(sf, scope)) == []
        sf = ctx.scan("deepspeed_tpu/serving/engine.py",
                      for_pass="zero-sync")
        for scope in ("_expire_deadlines", "_update_admission"):
            assert list(zero_sync.scope_violations(sf, scope)) == []


class TestLockDisciplinePass:
    FIXTURE = (
        "import threading\n"
        "\n"
        "class Box:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._items = []  # guarded-by: _lock\n"
        "\n"
        "    def _append(self, x):  # requires-lock: _lock\n"
        "        self._items.append(x)\n"
        "\n"
        "    def good(self, x):\n"
        "        with self._lock:\n"
        "            self._append(x)\n"
        "\n"
        "    def bad_unguarded(self):\n"
        "        return len(self._items)\n"
        "\n"
        "    def bad_call(self, x):\n"
        "        self._append(x)\n"
        "\n"
        "    def bad_blocking(self, fut):\n"
        "        with self._lock:\n"
        "            return fut.result()\n")

    def test_catches_all_three_shapes(self, tmp_path):
        sf, ctx = _scan(tmp_path, self.FIXTURE)
        finds = lock_discipline.check_scanned_file(sf, ctx, set())
        msgs = [f.message for f in finds]
        assert len(finds) == 3, msgs
        assert any("accessed without holding _lock in bad_unguarded"
                   in m for m in msgs)
        assert any("requires-lock _lock) without holding _lock in bad_call"
                   in m for m in msgs)
        assert any("blocking call" in m and "bad_blocking" in m
                   for m in msgs)

    def test_condition_wait_idiom_and_nonblocking_acquire_exempt(
            self, tmp_path):
        sf, ctx = _scan(tmp_path, (
            "import threading\n"
            "class P:\n"
            "    def __init__(self):\n"
            "        self._cond = threading.Condition()\n"
            "        self._n = 0  # guarded-by: _cond\n"
            "    def take(self):\n"
            "        with self._cond:\n"
            "            while self._n < 1:\n"
            "                self._cond.wait()\n"
            "            self._n -= 1\n"
            "    def probe(self, other):\n"
            "        with self._cond:\n"
            "            return other.acquire(blocking=False)\n"))
        assert lock_discipline.check_scanned_file(sf, ctx, set()) == []

    def test_nested_def_does_not_inherit_the_lock(self, tmp_path):
        sf, ctx = _scan(tmp_path, (
            "import threading\n"
            "class Q:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._n = 0  # guarded-by: _lock\n"
            "    def spawn(self):\n"
            "        with self._lock:\n"
            "            def worker():\n"
            "                return self._n\n"   # runs on another thread
            "            return worker\n"))
        finds = lock_discipline.check_scanned_file(sf, ctx, set())
        assert len(finds) == 1 and "_n" in finds[0].message

    def test_serving_tree_is_in_scope(self):
        """PR 12 widened the lock-discipline roots to the serving tier:
        the KV tiering manager (the one serving class with a real lock
        protocol) must be among the scanned files."""
        files = lock_discipline.checked_files(REPO_ROOT)
        rel = {os.path.relpath(f, REPO_ROOT).replace(os.sep, "/")
               for f in files}
        assert "deepspeed_tpu/serving/kv_tiering.py" in rel
        assert any(p.startswith("deepspeed_tpu/runtime/offload/")
                   for p in rel)

    def test_comm_recovery_plane_is_in_scope(self):
        """The recovery coordinator and the bounded-collective worker are
        lock-heavy host threading — the lock-discipline sweep must cover
        the comm tree."""
        files = lock_discipline.checked_files(REPO_ROOT)
        rel = {os.path.relpath(f, REPO_ROOT).replace(os.sep, "/")
               for f in files}
        assert "deepspeed_tpu/comm/recovery.py" in rel
        assert "deepspeed_tpu/comm/bounded.py" in rel

    def test_seeded_tiering_shape_violations(self, tmp_path):
        """A miniature of the kv_tiering lock protocol with the two bugs
        the pass exists to catch: a store read (blocking D2H/NVMe wait)
        under the manager lock, and a record-table mutation outside it."""
        sf, ctx = _scan(tmp_path, (
            "import threading\n"
            "class Tier:\n"
            "    def __init__(self, store):\n"
            "        self._lock = threading.Lock()\n"
            "        self._store = store\n"
            "        self._seqs = {}  # guarded-by: _lock\n"
            "    def bad_restage(self, rid, fut):\n"
            "        with self._lock:\n"
            "            rec = self._seqs[rid]\n"
            "            data = fut.result()\n"      # NVMe wait under lock
            "            return rec, data\n"
            "    def bad_discard(self, rid):\n"
            "        return self._seqs.pop(rid, None)\n"))
        finds = lock_discipline.check_scanned_file(sf, ctx, set())
        msgs = [f.message for f in finds]
        assert len(finds) == 2, msgs
        assert any("blocking call" in m and "bad_restage" in m for m in msgs)
        assert any("_seqs" in m and "bad_discard" in m for m in msgs)

    def test_serving_engine_is_in_scope(self):
        """PR 20's bounded-dispatch + incident recovery made engine.py and
        scheduler.py lock-adjacent host code (the BoundedCollective worker
        hand-off) — both must be under the lock-discipline sweep."""
        files = lock_discipline.checked_files(REPO_ROOT)
        rel = {os.path.relpath(f, REPO_ROOT).replace(os.sep, "/")
               for f in files}
        assert "deepspeed_tpu/serving/engine.py" in rel
        assert "deepspeed_tpu/serving/scheduler.py" in rel

    def test_seeded_incident_recovery_shape_violations(self, tmp_path):
        """A miniature of the serve-incident recovery protocol with the
        two bugs the pass exists to catch: waiting on the abandoned
        dispatch worker's future while holding the incident lock, and
        flipping the /healthz latch outside it."""
        sf, ctx = _scan(tmp_path, (
            "import threading\n"
            "class Engine:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._incident = None  # guarded-by: _lock\n"
            "    def bad_recover(self, worker_fut):\n"
            "        with self._lock:\n"
            "            self._incident = {'phase': 'decode'}\n"
            "            worker_fut.result()\n"       # wedged-worker wait
            "    def bad_clear(self):\n"
            "        self._incident = None\n"))
        finds = lock_discipline.check_scanned_file(sf, ctx, set())
        msgs = [f.message for f in finds]
        assert len(finds) == 2, msgs
        assert any("blocking call" in m and "bad_recover" in m for m in msgs)
        assert any("_incident" in m and "bad_clear" in m for m in msgs)

    def test_guard_naming_a_nonlock_is_flagged(self, tmp_path):
        sf, ctx = _scan(tmp_path, (
            "class R:\n"
            "    def __init__(self):\n"
            "        self._n = 0  # guarded-by: _mutex\n"
            "    def read(self):\n"
            "        return self._n\n"))
        finds = lock_discipline.check_scanned_file(sf, ctx, set())
        assert any("not a Lock/RLock/Condition" in f.message for f in finds)


class TestMonotonicPass:
    def test_seeded_wall_clock(self, tmp_path):
        p = tmp_path / "bad.py"
        p.write_text("import time\nt = time.time()\n")
        out = monotonic.check_files([str(p)])
        assert len(out) == 1 and "time.time()" in out[0]

    def test_legacy_pragma_sanctions(self, tmp_path):
        p = tmp_path / "ok.py"
        p.write_text("import time\n"
                     "a = time.time_ns()  # wall-clock anchor: alignment\n")
        assert monotonic.check_files([str(p)]) == []

    def test_docstring_mention_is_not_a_pragma(self, tmp_path):
        """The old substring check could be silenced by a docstring; the
        tokenize-based pragma engine only honors real comments."""
        p = tmp_path / "doc.py"
        p.write_text('import time\n'
                     'def f():\n'
                     '    "the wall-clock anchor idiom"; t = time.time()\n'
                     '    return t\n')
        assert len(monotonic.check_files([str(p)])) == 1


class TestOverlapPass:
    def test_seeded_gather_and_transfer(self, tmp_path):
        p = tmp_path / "bad.py"
        p.write_text("def _build_layered_step(x, y):\n"
                     "    g = all_gather(x)\n"
                     "    h = device_put(y)\n"
                     "    return g, h\n")
        out = overlap.check_files([(str(p), "_build_layered_step")])
        assert len(out) == 2
        assert any("gather primitive" in v for v in out)
        assert any("host-to-device transfer" in v for v in out)

    def test_vacuous_scope_guard(self, tmp_path):
        p = tmp_path / "gone.py"
        p.write_text("def something_else():\n    pass\n")
        out = overlap.check_files([(str(p), "_build_layered_step")])
        assert len(out) == 1 and "not found" in out[0]


class TestJaxprPass:
    def test_catches_pure_callback(self):
        import jax
        import jax.numpy as jnp

        def f(x):
            return jax.pure_callback(
                lambda a: np.asarray(a) * 2,
                jax.ShapeDtypeStruct(x.shape, x.dtype), x)

        closed = jax.make_jaxpr(f)(jnp.ones(4))
        finds, report = jaxpr_checks.analyze_jaxpr(closed, program="fx")
        assert any("pure_callback" in f.message for f in finds)
        assert report["clean"] is False

    def test_catches_divergent_cond_collectives(self):
        import jax
        import jax.numpy as jnp

        def f(x):
            return jax.lax.cond(x.sum() > 0,
                                lambda v: jax.lax.psum(v, "i"),
                                lambda v: v * 2.0, x)

        closed = jax.make_jaxpr(f, axis_env=[("i", 8)])(jnp.ones(4))
        finds, _ = jaxpr_checks.analyze_jaxpr(closed, program="fx")
        assert any("different collective sequences" in f.message
                   for f in finds)

    def test_catches_collective_in_while_body(self):
        import jax
        import jax.numpy as jnp

        def f(x):
            return jax.lax.while_loop(
                lambda c: c.sum() < 10.0,
                lambda c: jax.lax.psum(c, "i") * 0.4, x)

        closed = jax.make_jaxpr(f, axis_env=[("i", 8)])(jnp.ones(4))
        finds, _ = jaxpr_checks.analyze_jaxpr(closed, program="fx")
        assert any("while body" in f.message for f in finds)

    def test_clean_scan_collectives_pass_and_are_sequenced(self):
        import jax
        import jax.numpy as jnp

        def f(x):
            def body(c, _):
                return jax.lax.psum(c, "i"), None
            out, _ = jax.lax.scan(body, x, None, length=3)
            return jax.lax.psum(out, "i")

        closed = jax.make_jaxpr(f, axis_env=[("i", 8)])(jnp.ones(4))
        finds, report = jaxpr_checks.analyze_jaxpr(closed, program="fx")
        assert finds == []
        # static-trip scan collectives count once (symbolically), the
        # trailing psum appears in program order after it
        assert len(report["collectives"]) == 2
        assert report["collectives"][0].startswith("scan[")


class TestStalePragmaPass:
    def _run_monotonic_over(self, path, ctx):
        assert monotonic.check_files([str(path)], ctx=ctx) == []
        ctx.ran.append("monotonic")
        ctx.ran.append("stale-pragma")
        return stale_pragma.StalePragmaPass().run(ctx)

    def test_unconsumed_pragma_is_stale(self, tmp_path):
        p = tmp_path / "stale.py"
        # the sanctioned wall-clock call was removed; the pragma rotted
        p.write_text("import time\n"
                     "t = time.monotonic_ns()  # wall-clock anchor: old\n")
        finds = self._run_monotonic_over(p, core.Context())
        assert len(finds) == 1 and "stale pragma" in finds[0].message

    def test_live_pragma_not_flagged(self, tmp_path):
        p = tmp_path / "live.py"
        p.write_text("import time\n"
                     "t = time.time_ns()  # wall-clock anchor: alignment\n")
        assert self._run_monotonic_over(p, core.Context()) == []

    def test_unknown_pass_and_missing_reason_warn(self, tmp_path):
        p = tmp_path / "odd.py"
        p.write_text("import time\n"
                     "a = 1  # dslint: ok(nonexistent-pass) - typo\n"
                     "b = time.monotonic_ns()  # dslint: ok(monotonic)\n")
        ctx = core.Context()
        monotonic.check_files([str(p)], ctx=ctx)
        ctx.ran.append("monotonic")
        finds = stale_pragma.StalePragmaPass().run(ctx)
        msgs = [f.message for f in finds]
        assert any("unknown pass" in m for m in msgs)
        assert any("no reason" in m for m in msgs)


# --------------------------------------------------------------------------- #
# PR 19: the autotuner's trial-scoring path joins the zero-sync roots and
# the scheduler bookkeeping joins the lock-discipline sweep
# --------------------------------------------------------------------------- #

class TestAutotuningStaticAnalysis:
    def test_trial_scoring_scopes_are_guarded(self):
        """The closed loop's scoring module (whole file) and search body
        are in the zero-sync roster — candidate ranking must stay pure
        host-side JSON arithmetic."""
        scopes = set(zero_sync.CHECKED_SCOPES)
        assert ("deepspeed_tpu/autotuning/scoring.py", None) in scopes
        assert ("deepspeed_tpu/autotuning/loop.py", "tune") in scopes

    def test_seeded_sync_in_scoring_path_is_flagged(self, tmp_path):
        """A seeded violation in a tune()-style loop — scoring a trial
        off a live engine's device values instead of its EFFICIENCY.json
        artifact — is caught."""
        sf, _ = _scan(tmp_path, (
            "class Loop:\n"
            "    def tune(self, engine):\n"
            "        gf = float(engine.ledger_goodput)\n"
            "        wall = engine.wall_s.item()\n"
            "        return gf / wall\n"))
        msgs = [m for _, m in zero_sync.scope_violations(sf, "tune")]
        assert len(msgs) == 2, msgs
        assert any("float()" in m for m in msgs)
        assert any(".item()" in m for m in msgs)

    def test_live_scoring_path_is_clean(self):
        """The real scoring.py (modulo its JSON-scalar pragmas) and
        loop.tune() pass the zero-sync check."""
        ctx = core.Context()
        sf = ctx.scan("deepspeed_tpu/autotuning/scoring.py",
                      for_pass="zero-sync")
        out = [(ln, m) for ln, m in zero_sync.scope_violations(sf, None)
               if not ctx.sanctioned(sf, ln, "zero-sync")]
        assert out == []
        sf = ctx.scan("deepspeed_tpu/autotuning/loop.py",
                      for_pass="zero-sync")
        assert list(zero_sync.scope_violations(sf, "tune")) == []

    def test_autotuning_tree_is_in_lock_scope(self):
        """The trial scheduler's cross-thread bookkeeping put the
        autotuning tree into the lock-discipline sweep."""
        files = lock_discipline.checked_files(REPO_ROOT)
        rel = {os.path.relpath(f, REPO_ROOT).replace(os.sep, "/")
               for f in files}
        assert "deepspeed_tpu/autotuning/scheduler.py" in rel
        assert "deepspeed_tpu/autotuning/loop.py" in rel

    def test_seeded_scheduler_bookkeeping_violations(self, tmp_path):
        """A miniature TrialScheduler with the two bugs the pass exists
        to catch: the results table mutated outside its lock, and the
        child wait (a whole trial's runtime!) issued under it."""
        sf, ctx = _scan(tmp_path, (
            "import threading\n"
            "class Sched:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self.results = []  # guarded-by: _lock\n"
            "    def bad_record(self, r):\n"
            "        self.results.append(r)\n"
            "    def bad_wait(self, proc):\n"
            "        with self._lock:\n"
            "            return proc.wait(timeout=600)\n"))
        finds = lock_discipline.check_scanned_file(sf, ctx, set())
        msgs = [f.message for f in finds]
        assert len(finds) == 2, msgs
        assert any("results" in m and "bad_record" in m for m in msgs)
        assert any("blocking call" in m and "bad_wait" in m for m in msgs)

    def test_live_scheduler_is_clean(self):
        """The real scheduler.py honors its own lock protocol: guarded
        dicts only touched under _lock, the trial wait outside it."""
        ctx = core.Context()
        sf = ctx.scan("deepspeed_tpu/autotuning/scheduler.py",
                      for_pass="lock-discipline")
        assert lock_discipline.check_scanned_file(sf, ctx, set()) == []


# --------------------------------------------------------------------------- #
# the race the triage found: get() vs concurrent put()
# --------------------------------------------------------------------------- #

class TestStoreGetPutRace:
    def test_sync_read_does_not_clobber_concurrent_put(self, tmp_path):
        """A get() that fell back to a synchronous NVMe read must not
        overwrite (nor return) a host copy installed by a put() that
        landed while the read was blocked on disk — the disk bytes
        predate the put and are stale."""
        from deepspeed_tpu.runtime.offload.staging import StagingPool
        from deepspeed_tpu.runtime.offload.store import TieredStore
        pool = StagingPool(str(tmp_path / "stage"))
        store = TieredStore(pool)
        old = np.zeros(4, np.float32)
        new = np.ones(4, np.float32)
        store.put("k", old)
        store.drain()
        with store._lock:           # force the NVMe path on the next get
            store._host.clear()
            store._host_bytes = 0

        real_read = pool.read_sync

        def racy_read(key):         # a writer lands mid-read
            data = real_read(key)
            store.put(key, new, write_through=False)
            return data

        pool.read_sync = racy_read
        try:
            got = store.get("k")
        finally:
            pool.read_sync = real_read
        np.testing.assert_array_equal(got, new)
        np.testing.assert_array_equal(store.get("k"), new)
        pool.close()


# --------------------------------------------------------------------------- #
# pallas-discipline (PR 14): static trip counts + predicated DMA pairing
# --------------------------------------------------------------------------- #

_KERNEL_FIXTURE = (
    "import jax\n"
    "from jax import lax\n"
    "from jax.experimental import pallas as pl\n"
    "\n"
    "def bad_trip(pos_ref, o_ref):\n"
    "    nk = (pos_ref[0] + 7) // 8\n"
    "    lax.fori_loop(0, nk, lambda i, c: c, 0)\n"
    "\n"
    "def bad_trip_direct(pos_ref, o_ref):\n"
    "    lax.fori_loop(0, pl.load(pos_ref, (0,)), lambda i, c: c, 0)\n"
    "\n"
    "def good_trip(x_ref, o_ref, *, nk_max):\n"
    "    nk = pl.cdiv(x_ref.shape[0], 8)\n"
    "    lax.fori_loop(0, nk_max, lambda i, c: c, 0)\n"
    "    lax.fori_loop(0, nk, lambda i, c: c, 0)\n"
    "\n"
    "def bad_dma(cp, pred, c):\n"
    "    return lax.cond(pred, lambda x: cp.start(), lambda x: cp.wait(), c)\n"
    "\n"
    "def good_dma(cp, pred, c):\n"
    "    def live(x):\n"
    "        cp.start()\n"
    "        cp.wait()\n"
    "        return x\n"
    "    return lax.cond(pred, live, lambda x: x, c)\n")


class TestPallasDisciplinePass:
    def test_flags_data_dependent_trip_counts(self, tmp_path):
        sf, _ = _scan(tmp_path, _KERNEL_FIXTURE)
        msgs = [m for _, m in pallas_discipline.fori_violations(sf)]
        assert len(msgs) == 2, msgs
        assert all("data-dependent" in m for m in msgs)

    def test_static_and_shape_derived_bounds_are_clean(self, tmp_path):
        sf, _ = _scan(tmp_path, _KERNEL_FIXTURE)
        lines = [ln for ln, _ in pallas_discipline.fori_violations(sf)]
        src_lines = _KERNEL_FIXTURE.splitlines()
        for ln in lines:
            assert "good" not in src_lines[ln - 1]

    def test_flags_unpaired_dma_across_cond_branches(self, tmp_path):
        sf, _ = _scan(tmp_path, _KERNEL_FIXTURE)
        msgs = [m for _, m in pallas_discipline.dma_violations(sf)]
        # both branches of bad_dma are unbalanced (1/0 and 0/1); good_dma's
        # live() branch is 1/1 and its identity branch 0/0
        assert len(msgs) == 2, msgs
        assert any("true branch" in m for m in msgs)
        assert any("false branch" in m for m in msgs)

    def test_named_branch_functions_are_resolved(self, tmp_path):
        sf, _ = _scan(tmp_path, (
            "from jax import lax\n"
            "def leak(x):\n"
            "    cp.start()\n"
            "    return x\n"
            "def k(cp, pred, c):\n"
            "    return lax.cond(pred, leak, lambda x: x, c)\n"))
        msgs = [m for _, m in pallas_discipline.dma_violations(sf)]
        assert len(msgs) == 1 and "1 DMA start() but 0 wait()" in msgs[0]

    def test_pragma_opt_out(self, tmp_path):
        src = (
            "from jax import lax\n"
            "def k(pos_ref, o_ref):\n"
            "    n = pos_ref[0]\n"
            "    # dslint: ok(pallas-discipline) - bounded by grid above\n"
            "    lax.fori_loop(0, n, lambda i, c: c, 0)\n")
        sf, ctx = _scan(tmp_path, src)
        viol = list(pallas_discipline.fori_violations(sf))
        assert len(viol) == 1
        lineno = viol[0][0]
        assert ctx.sanctioned(sf, lineno, "pallas-discipline")

    def test_repo_kernels_clean(self):
        findings, _ = core.run_passes(only=["pallas-discipline"])
        assert findings == [], "\n".join(f.format() for f in findings)
        # the pass actually scanned the kernel dir (not vacuously clean)
        rels = pallas_discipline.kernel_files(core.REPO_ROOT)
        assert any(r.endswith("decode_attention.py") for r in rels)
        assert any(r.endswith("cross_entropy.py") for r in rels)
        assert any(r.endswith("fused_optim.py") for r in rels)


# ---- the tests' own harness stays ONE harness -------------------------------- #
def test_the_serving_tests_share_one_harness():
    """``tests/unit/serving_helpers.py`` holds the one ``Driver``, the one
    ``Recording`` and the one ``served_logits``: a copy in a test file builds
    a new jitted function (or engine) a case, which is what cost the suite
    half its time (ROADMAP D9).  And ``test_chip_compile.py`` compiles ahead
    of time in ONE place, ``_compile``.  Reads sources; runs nothing."""
    import re
    unit = os.path.join(REPO_ROOT, "tests", "unit")
    copies = re.compile(r"^(class Driver\b|class Recording\b|def served_logits\b)", re.M)
    found = {}
    for folder, _, names in os.walk(unit):
        for name in names:
            if name.endswith(".py"):
                path = os.path.join(folder, name)
                with open(path) as f:
                    hits = copies.findall(f.read())
                if hits:
                    found[os.path.relpath(path, unit)] = sorted(hits)
    assert found == {"serving_helpers.py": [
        "class Driver", "class Recording", "def served_logits"]}, found
    with open(os.path.join(unit, "ops", "test_chip_compile.py")) as f:
        source = f.read()
    assert len(re.findall(r"\.compile\(\)", source)) == 1
    assert len(re.findall(r"\.lower\(", source)) == 1
