"""Serving e2e against the live observability plane: an ops server
scraped over HTTP *mid-run* must already show populated TTFT/latency
histograms and live arena gauges, /healthz must be healthy with the
serve_arena check registered, and the compiled-program contract must
survive the instrumentation (metrics land off the jitted hot path)."""

import json
import re
import urllib.request

import numpy as np
import pytest

import jax

from deepspeed_tpu.models.gpt import GPT, GPTConfig
from deepspeed_tpu.runtime.config import DeepSpeedTelemetryConfig
from deepspeed_tpu.serving import DeepSpeedServingConfig, ServingEngine
from deepspeed_tpu.telemetry.hub import TelemetryHub


@pytest.fixture(scope="module")
def tiny_model():
    cfg = GPTConfig(vocab_size=128, n_positions=128, n_embd=32, n_layer=2,
                    n_head=4, dtype="float32")
    model = GPT(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return model, params


def _get(url):
    with urllib.request.urlopen(url, timeout=5) as r:
        return r.status, r.read().decode()


def test_mid_run_scrape_shows_live_serving_metrics(tiny_model, tmp_path):
    model, params = tiny_model
    hub = TelemetryHub.from_config(DeepSpeedTelemetryConfig(
        enabled=True, jsonl_path=str(tmp_path / "telemetry.jsonl"),
        flush_every=2, ops_server=True, ops_port=0))
    scfg = DeepSpeedServingConfig(block_size=8, num_blocks=64,
                                  max_batch_size=4, prefill_chunk=16,
                                  telemetry_every=2, dtype="float32")
    eng = ServingEngine(model, config=scfg, params=params, telemetry=hub)
    url = hub.obs_server.url

    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(1, 128, size=n)) for n in (5, 9, 7, 12)]
    futs = [eng.submit(p, max_new_tokens=6) for p in prompts]

    # drive until half the requests finished and the periodic flush has
    # drained their events, then scrape MID-RUN: the engine is still holding
    # arena blocks and decoding
    while (sum(f.done for f in futs) < 2
           or eng.step_count % scfg.telemetry_every):
        eng.step()
    assert not all(f.done for f in futs)

    code, text = _get(f"{url}/metrics")
    assert code == 200
    m = re.search(r"^dstpu_serve_ttft_ms_count (\d+)", text, re.MULTILINE)
    assert m and int(m.group(1)) >= 2       # TTFT histogram populated live
    m = re.search(r"^dstpu_serve_blocks_in_use (\d+)", text, re.MULTILINE)
    assert m and int(m.group(1)) > 0        # arena occupancy is live
    assert "dstpu_serve_kv_host_bytes" in text
    assert "dstpu_serve_kv_nvme_bytes" in text
    assert "dstpu_serve_step_ms_count" in text

    code, body = _get(f"{url}/healthz")
    health = json.loads(body)
    assert code == 200 and health["healthy"]
    arena = health["checks"]["serve_arena"]
    assert arena["ok"] and arena["blocks_in_use"] > 0

    eng.run()
    assert all(f.done for f in futs)
    assert eng.compiled_programs() == 1     # instrumentation stayed host-side

    # post-run: drained counters agree with the scheduler's view
    hub.flush()
    snap = hub.registry.snapshot()
    assert snap["counters"]["serve_finished_total"]["value"] == len(futs)
    assert snap["histograms"]["serve_ttft_ms"]["count"] == len(futs)
    eng.close()
    hub.close()


def test_engine_registers_gauges_without_ops_server(tiny_model, tmp_path):
    """metrics-only config (no HTTP server): the engine still feeds the
    registry; nothing listens, nothing breaks."""
    model, params = tiny_model
    hub = TelemetryHub.from_config(DeepSpeedTelemetryConfig(
        enabled=True, jsonl_path=str(tmp_path / "t.jsonl"), flush_every=2))
    assert hub.obs_server is None and hub.registry is not None
    scfg = DeepSpeedServingConfig(block_size=8, num_blocks=64,
                                  max_batch_size=4, prefill_chunk=16,
                                  telemetry_every=2, dtype="float32")
    eng = ServingEngine(model, config=scfg, params=params, telemetry=hub)
    f = eng.submit([1, 2, 3], max_new_tokens=4)
    eng.run()
    assert f.done
    hub.flush()
    snap = hub.registry.snapshot()
    assert snap["histograms"]["serve_step_ms"]["count"] > 0
    assert snap["counters"]["serve_finished_total"]["value"] == 1
    # what the engine keeps in a layout of its own: nothing of this stack
    assert snap["gauges"]["serve_relaid_leaves"]["value"] == eng.relaid_leaves == 0
    assert snap["gauges"]["serve_relaid_bytes"]["value"] == eng.relaid_bytes == 0
    eng.close()
    hub.close()
