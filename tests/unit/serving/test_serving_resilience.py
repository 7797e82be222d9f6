"""Serving resilience plane: deadlines, adaptive shedding, wedge recovery.

Three survive-the-bad-day contracts, each proven end-to-end on the tiny
GPT:

* deadline expiry cancels at the step boundary, frees the arena blocks
  (``check_consistent`` after every cancellation) and books the wasted
  prefill into the goodput ledger;
* the shed ladder degrades weakest-class-first — batch sheds while
  realtime keeps flowing, and the ladder de-escalates with hysteresis;
* a wedged compiled step raises ``ServeStepTimeout`` *after* in-process
  recovery: compiled programs dropped, arena rebuilt, every in-flight
  request requeued with ``prefilled=0`` — and the drained token streams
  are still exactly sequential ``generate()``'s, with zero requests lost.
"""

import numpy as np
import pytest

import jax

from deepspeed_tpu.models.gpt import GPT, GPTConfig
from deepspeed_tpu.serving import DeepSpeedServingConfig, ServingEngine
from deepspeed_tpu.serving.engine import ServeStepTimeout
from deepspeed_tpu.serving.scheduler import (
    EXPIRED, SHED_LEVELS, AdmissionController, DeadlineExceeded, ShedError,
)
from deepspeed_tpu.telemetry.hub import RingBufferSink, TelemetryHub
from deepspeed_tpu.telemetry.ledger import GoodputLedger
from deepspeed_tpu.testing import fault_injection as fi
from tests.unit.serving_helpers import (  # noqa: F401  (a fixture among them)
    deadline_on_the_wedge_alone, sequential_tokens)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = GPTConfig(vocab_size=128, n_positions=128, n_embd=32, n_layer=2,
                    n_head=4, dtype="float32")
    model = GPT(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return model, params


@pytest.fixture(autouse=True)
def _clean_fault_plan():
    yield
    fi.clear_plan()


# sequential ``generate()``, one program a model (``serving_helpers.py``)
sequential_reference = sequential_tokens


class FakeClock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t

    def advance(self, s):
        self.t += s


# --------------------------------------------------------------------- #
# admission ladder units (pure host, no engine)
# --------------------------------------------------------------------- #

def _adm(**kw):
    base = dict(block_size=8, num_blocks=16, queue_age_watermark_ms=100.0,
                shed_recovery_steps=3, brownout_max_new_tokens=4)
    base.update(kw)
    return AdmissionController(DeepSpeedServingConfig(**base))


def test_ladder_escalates_immediately_and_recovers_with_hysteresis():
    adm = _adm()
    assert adm.level == 0 and adm.level_name == "ok"
    # queue age past 4x the watermark jumps straight to the top rung
    assert adm.evaluate(0.5) == 3 and adm.level_name == "shed_standard"
    # one calm evaluation must NOT step down (hysteresis)
    assert adm.evaluate(0.0) == 3
    assert adm.evaluate(0.0) == 3
    assert adm.evaluate(0.0) == 2        # 3rd calm eval: one rung only
    # renewed pressure resets the calm counter
    assert adm.evaluate(0.0) == 2
    assert adm.evaluate(0.25) == 2       # age > 2x wm holds the rung
    assert adm.evaluate(0.0) == 2        # calm count restarted
    assert adm.evaluate(0.0) == 2
    assert adm.evaluate(0.0) == 1 and adm.brownout
    for _ in range(3):
        adm.evaluate(0.0)
    assert adm.level == 0


def test_ladder_burn_signals_and_watermark_combine():
    adm = _adm()
    assert adm.evaluate(0.0, "burn_slow") == 1
    assert adm.evaluate(0.0, "burn_fast") == 2
    # the worse of the two signals wins
    assert adm.evaluate(0.45, "burn_slow") == 3
    adm2 = _adm(queue_age_watermark_ms=0.0)   # watermark disabled
    assert adm2.evaluate(100.0) == 0          # age alone can't trip it
    assert adm2.evaluate(100.0, "burn_fast") == 2


def test_ladder_sheds_weakest_class_first():
    adm = _adm()
    adm.evaluate(0.25)                        # age > 2x wm -> shed_batch
    assert adm.level == 2
    assert not adm.admit_ok("batch")
    assert adm.admit_ok("standard") and adm.admit_ok("realtime")
    adm.evaluate(0.5)                         # -> shed_standard
    assert not adm.admit_ok("batch") and not adm.admit_ok("standard")
    assert adm.admit_ok("realtime"), "realtime is never ladder-shed"
    assert adm.shed_counts["batch"] == 2 and adm.shed_counts["standard"] == 1


def test_brownout_caps_token_budget():
    adm = _adm()
    assert adm.cap_new_tokens(32) == 32       # level 0: no cap
    adm.evaluate(0.15)                        # -> brownout
    assert adm.brownout and adm.cap_new_tokens(32) == 4
    assert adm.cap_new_tokens(2) == 2         # never raises a budget
    no_cap = _adm(brownout_max_new_tokens=0)
    no_cap.evaluate(0.15)
    assert no_cap.cap_new_tokens(32) == 32    # cap disabled


# --------------------------------------------------------------------- #
# deadlines
# --------------------------------------------------------------------- #

def test_deadline_expiry_frees_blocks_and_books_waste(tiny_model):
    model, params = tiny_model
    ring = RingBufferSink(capacity=1024)
    hub = TelemetryHub(sinks=[ring], flush_every=0)
    hub.ledger = GoodputLedger()
    scfg = DeepSpeedServingConfig(block_size=8, num_blocks=32,
                                  max_batch_size=4, prefill_chunk=8,
                                  dtype="float32",
                                  deadline_ms={"batch": 1000.0})
    eng = ServingEngine(model, config=scfg, params=params, telemetry=hub)
    clock = FakeClock()
    eng._clock = clock

    rng = np.random.default_rng(3)
    doomed = eng.submit(list(rng.integers(1, 128, size=12)),
                        max_new_tokens=30, slo="batch")
    keeper = eng.submit(list(rng.integers(1, 128, size=6)),
                        max_new_tokens=4, slo="realtime")
    for _ in range(4):                           # realtime prefills first
        eng.step()
        if doomed.request.prefilled > 0:
            break
    assert doomed.request.prefilled > 0
    wasted = doomed.request.prefilled
    before = eng.alloc.blocks_in_use
    assert before > 0

    clock.advance(1.5)                           # past the 1s batch budget
    eng.step()
    assert doomed.request.state == EXPIRED
    assert doomed.request.slot == -1
    assert eng.alloc.blocks_in_use < before      # its blocks came back
    eng.alloc.check_consistent()
    assert eng.sched.expired_count == 1
    with pytest.raises(DeadlineExceeded):
        doomed.result()
    # the realtime request (no deadline configured) is untouched
    assert keeper.result() == sequential_reference(
        model, params, keeper.request.prompt, 4)

    hub.flush()
    ev = [r for r in ring.of_kind("serve_expired")]
    assert len(ev) == 1 and ev[0]["rid"] == doomed.request.rid
    assert ev[0]["slo"] == "batch"
    assert ev[0]["age_ms"] >= ev[0]["deadline_ms"] > 0
    assert ev[0]["wasted_prefill_tokens"] == wasted
    serve = hub.ledger.snapshot()["serve"]
    assert serve["by_slo"]["batch"]["expired"] == 1
    assert serve["wasted_prefill_tokens"] >= wasted
    eng.close()


def test_waiting_request_expires_without_ever_owning_blocks(tiny_model):
    """Cancellation of a never-admitted request must be clean: no slot, no
    blocks, no tier records — free/discard are idempotent no-ops."""
    model, params = tiny_model
    scfg = DeepSpeedServingConfig(block_size=8, num_blocks=32,
                                  max_batch_size=1, prefill_chunk=8,
                                  dtype="float32",
                                  deadline_ms={"batch": 500.0})
    eng = ServingEngine(model, config=scfg, params=params)
    clock = FakeClock()
    eng._clock = clock
    hog = eng.submit([1, 2, 3, 4], max_new_tokens=20)   # takes the one slot
    eng.step()
    parked = eng.submit([5, 6, 7], max_new_tokens=4, slo="batch")
    clock.advance(1.0)
    eng.step()
    assert parked.request.state == EXPIRED
    assert parked.request.prefilled == 0
    eng.alloc.check_consistent()
    assert hog.result() == sequential_reference(model, params,
                                                [1, 2, 3, 4], 20)
    eng.close()


# --------------------------------------------------------------------- #
# shedding e2e
# --------------------------------------------------------------------- #

def test_overload_sheds_batch_only_and_recovers(tiny_model):
    model, params = tiny_model
    ring = RingBufferSink(capacity=2048)
    hub = TelemetryHub(sinks=[ring], flush_every=0)
    scfg = DeepSpeedServingConfig(block_size=8, num_blocks=64,
                                  max_batch_size=2, prefill_chunk=8,
                                  dtype="float32",
                                  queue_age_watermark_ms=100.0,
                                  brownout_max_new_tokens=4,
                                  shed_recovery_steps=2)
    eng = ServingEngine(model, config=scfg, params=params, telemetry=hub)
    clock = FakeClock()
    eng._clock = clock

    rng = np.random.default_rng(4)
    first = eng.submit(list(rng.integers(1, 128, size=6)), max_new_tokens=6)
    backlog = [eng.submit(list(rng.integers(1, 128, size=6)),
                          max_new_tokens=6) for _ in range(3)]
    clock.advance(0.25)               # oldest waiting age > 2x watermark
    eng.step()
    assert eng.admission.level == 2 and eng.admission.level_name == "shed_batch"

    # batch is rejected, realtime flows; brownout caps admitted budgets
    with pytest.raises(ShedError) as ei:
        eng.submit([1, 2, 3], max_new_tokens=6, slo="batch")
    assert ei.value.slo == "batch" and ei.value.level == 2
    rt = eng.submit(list(rng.integers(1, 128, size=4)),
                    max_new_tokens=16, slo="realtime")
    assert rt.request.max_new_tokens == 4, "brownout caps the budget"

    eng.run()                         # drain: queue age falls to zero
    for _ in range(4):                # calm evaluations step the rung down
        eng.step()
    assert eng.admission.level == 0
    assert eng.submit([1, 2], max_new_tokens=2, slo="batch").result() \
        == sequential_reference(model, params, [1, 2], 2)

    hub.flush()
    rej = [r for r in ring.of_kind("serve_shed")
           if r.get("event") == "rejected"]
    assert len(rej) == 1 and rej[0]["slo"] == "batch"
    levels = [r for r in ring.of_kind("serve_shed")
              if r.get("event") == "level"]
    assert any(r["to"] == "shed_batch" for r in levels)
    assert any(r["to"] == "ok" for r in levels)
    # every admitted request still finished, token-identical
    for f in [first] + backlog + [rt]:
        p, m = f.request.prompt, f.request.max_new_tokens
        assert f.token_ids == sequential_reference(model, params, p, m)
    eng.close()


def test_shed_level_gauge_fed_via_metrics_sink(tiny_model):
    from deepspeed_tpu.telemetry.metrics import (
        MetricsRegistry, MetricsSink, render_prometheus)
    model, params = tiny_model
    reg = MetricsRegistry()
    hub = TelemetryHub(sinks=[MetricsSink(reg)], flush_every=0)
    scfg = DeepSpeedServingConfig(block_size=8, num_blocks=32,
                                  max_batch_size=2, prefill_chunk=8,
                                  dtype="float32",
                                  queue_age_watermark_ms=50.0)
    eng = ServingEngine(model, config=scfg, params=params, telemetry=hub)
    clock = FakeClock()
    eng._clock = clock
    eng.submit([1, 2, 3], max_new_tokens=4)
    eng.submit([4, 5, 6], max_new_tokens=4)
    clock.advance(0.25)                        # > 4x watermark
    eng.step()
    with pytest.raises(ShedError):
        eng.submit([7], max_new_tokens=2, slo="standard")
    hub.flush()
    text = render_prometheus(reg.snapshot())
    assert "dstpu_serve_shed_level 3" in text
    assert 'dstpu_serve_shed_total{slo="standard"} 1' in text
    eng.close()


# --------------------------------------------------------------------- #
# wedge incidents
# --------------------------------------------------------------------- #

def test_wedged_step_recovers_token_identical(tiny_model, deadline_on_the_wedge_alone):
    model, params = tiny_model
    ring = RingBufferSink(capacity=2048)
    hub = TelemetryHub(sinks=[ring], flush_every=0)
    hub.ledger = GoodputLedger()
    scfg = DeepSpeedServingConfig(block_size=8, num_blocks=64,
                                  max_batch_size=4, prefill_chunk=8,
                                  dtype="float32",
                                  serve_step_timeout_s=0.5)
    eng = ServingEngine(model, config=scfg, params=params, telemetry=hub)
    eng.submit([1, 2], max_new_tokens=2).result()   # warm the program

    rng = np.random.default_rng(5)
    lens = (6, 11, 4, 9)
    mnts = (8, 5, 10, 7)
    prompts = [list(rng.integers(1, 128, size=n)) for n in lens]
    futs = [eng.submit(p, max_new_tokens=m) for p, m in zip(prompts, mnts)]
    eng.step()                                 # real progress pre-wedge
    assert eng.sched.active

    fi.install_plan([{"site": "serve.step", "action": "wedge", "on_hit": 1}])
    with pytest.raises(ServeStepTimeout) as ei:
        eng.step()
    assert ei.value.deadline_s == pytest.approx(0.5)
    # recovery already happened: requests requeued, none lost, latched
    assert eng.incident_count == 1
    assert not eng.sched.active and len(eng.sched.waiting) == len(futs)
    assert all(r.prefilled == 0 for r in eng.sched.waiting)
    assert eng._incident_health()["ok"] is False
    eng.alloc.check_consistent()

    eng.run()                                  # drain through the rebuild
    assert eng._incident_health()["ok"] is True, "first clean step clears"
    for p, m, f in zip(prompts, mnts, futs):
        assert f.done
        assert f.token_ids == sequential_reference(model, params, p, m)
    assert eng.compiled_programs() == 1

    hub.flush()
    ev = ring.of_kind("serve_incident")
    events = [r["event"] for r in ev]
    assert events[:2] == ["begin", "recovered"] and "cleared" in events
    rec = next(r for r in ev if r["event"] == "recovered")
    assert rec["lost"] == 0 and rec["requeued"] == len(futs)
    assert rec["phase"] in ("prefill", "decode")
    # wedge wait + rebuild are booked as incident seconds, not goodput
    snap = hub.ledger.snapshot()
    assert snap["categories"]["comm_recovery"] >= 0.5
    eng.close()


def test_result_tolerates_wedge_and_timeout_s_bounds_the_wait(
        tiny_model, deadline_on_the_wedge_alone):
    model, params = tiny_model
    scfg = DeepSpeedServingConfig(block_size=8, num_blocks=32,
                                  max_batch_size=2, prefill_chunk=8,
                                  dtype="float32",
                                  serve_step_timeout_s=0.4)
    eng = ServingEngine(model, config=scfg, params=params)
    eng.submit([1, 2], max_new_tokens=2).result()   # warm the program
    fi.install_plan([{"site": "serve.step", "action": "wedge", "on_hit": 2}])
    fut = eng.submit([3, 1, 4, 1, 5], max_new_tokens=6)
    # result() rides through the mid-drain incident transparently
    assert fut.result() == sequential_reference(model, params,
                                                [3, 1, 4, 1, 5], 6)
    assert eng.incident_count == 1
    fi.clear_plan()

    slow = eng.submit([2, 7, 1], max_new_tokens=8)
    with pytest.raises(TimeoutError):
        slow.result(timeout_s=0.0)             # wall-clock bound, not steps
    assert slow.result(timeout_s=30.0) == sequential_reference(
        model, params, [2, 7, 1], 8)
    eng.close()


def test_unbounded_engine_has_no_dispatch_worker(tiny_model):
    """serve_step_timeout_s=0 (the default) must keep the old inline
    dispatch — no worker thread, no timeout machinery."""
    model, params = tiny_model
    scfg = DeepSpeedServingConfig(block_size=8, num_blocks=32,
                                  max_batch_size=2, dtype="float32")
    eng = ServingEngine(model, config=scfg, params=params)
    assert eng._bounded is None
    assert eng.submit([9, 8, 7], max_new_tokens=3).result() \
        == sequential_reference(model, params, [9, 8, 7], 3)
    eng.close()
    eng.close()                                # idempotent


def test_restage_fault_site_forces_recompute(tiny_model):
    """A scripted serve.restage failure degrades to the recompute path —
    outputs stay token-identical (the pre-tiering contract)."""
    model, params = tiny_model
    scfg = DeepSpeedServingConfig(block_size=4, num_blocks=10,
                                  max_batch_size=4, prefill_chunk=8,
                                  max_blocks_per_seq=9, dtype="float32",
                                  kv_tiering=True)
    eng = ServingEngine(model, config=scfg, params=params)
    fi.install_plan([{"site": "serve.restage", "action": "raise",
                      "times": 100}])
    rng = np.random.default_rng(6)
    lens = (10, 14, 6, 12, 9)
    mnts = (16, 12, 20, 10, 14)
    prompts = [list(rng.integers(1, 128, size=n)) for n in lens]
    futs = [eng.submit(p, max_new_tokens=m) for p, m in zip(prompts, mnts)]
    eng.run()
    assert eng.sched.preemption_count > 0, "arena pressure must preempt"
    for p, m, f in zip(prompts, mnts, futs):
        assert f.token_ids == sequential_reference(model, params, p, m)
    eng.close()


def test_new_fault_sites_validate():
    fi.install_plan([{"site": "serve.step", "action": "wedge"},
                     {"site": "serve.restage", "action": "raise"}])
    fi.clear_plan()
    with pytest.raises(ValueError):
        fi.install_plan([{"site": "serve.steps", "action": "wedge"}])


# --------------------------------------------------------------------- #
# warm restart
# --------------------------------------------------------------------- #

def test_snapshot_restore_round_trip_token_identical(tiny_model):
    model, params = tiny_model
    scfg = DeepSpeedServingConfig(block_size=8, num_blocks=64,
                                  max_batch_size=4, prefill_chunk=8,
                                  dtype="float32",
                                  deadline_ms={"batch": 60000.0})
    eng = ServingEngine(model, config=scfg, params=params)
    rng = np.random.default_rng(7)
    lens = (5, 12, 8)
    mnts = (10, 6, 12)
    prompts = [list(rng.integers(1, 128, size=n)) for n in lens]
    futs = [eng.submit(p, max_new_tokens=m, slo=s)
            for p, m, s in zip(prompts, mnts,
                               ("standard", "batch", "realtime"))]
    for _ in range(4):                # partial progress: some tokens out
        eng.step()
    assert any(f.request.generated for f in futs)

    snap = eng.snapshot()
    assert snap["schema"] == 1 and len(snap["requests"]) == 3
    batch = next(d for d in snap["requests"] if d["slo"] == "batch")
    assert 0 < batch["deadline_remaining_s"] <= 60.0

    import json
    snap = json.loads(json.dumps(snap))        # must survive serialization
    eng.close()

    eng2 = ServingEngine(model, config=scfg, params=params)
    futs2 = eng2.restore(snap)
    assert [f.request.rid for f in futs2] == [f.request.rid for f in futs]
    eng2.run()
    for p, m, f in zip(prompts, mnts, futs2):
        assert f.token_ids == sequential_reference(model, params, p, m)
    eng2.alloc.check_consistent()
    # restored deadline re-anchored to the new engine's clock
    rb = next(f for f in futs2 if f.request.slo == "batch")
    assert rb.request.state != EXPIRED
    eng2.close()


def test_restore_requires_idle_engine(tiny_model):
    model, params = tiny_model
    scfg = DeepSpeedServingConfig(block_size=8, num_blocks=32,
                                  max_batch_size=2, dtype="float32")
    eng = ServingEngine(model, config=scfg, params=params)
    eng.submit([1, 2], max_new_tokens=2)
    with pytest.raises(AssertionError):
        eng.restore({"schema": 1, "requests": []})
    eng.close()


def test_shed_levels_constant_shape():
    assert SHED_LEVELS == ("ok", "brownout", "shed_batch", "shed_standard")
