"""End-to-end serving acceptance tests.

The contract: continuous batching is an *engine-side* optimization — the
tokens must be exactly what sequential ``generate()`` would produce.  With
fp32 numerics the paged step is bit-identical to the dense-cache path
(masked positions contribute exactly 0.0 after softmax), so greedy outputs
match token-for-token, including across evict→recompute cycles.
"""

import numpy as np
import pytest

import jax

from deepspeed_tpu.models.gpt import GPT, GPTConfig
from deepspeed_tpu.serving import DeepSpeedServingConfig, ServingEngine
from deepspeed_tpu.telemetry.hub import RingBufferSink, TelemetryHub
from tests.unit.serving_helpers import sequential_tokens


@pytest.fixture(scope="module")
def tiny_model():
    cfg = GPTConfig(vocab_size=128, n_positions=128, n_embd=32, n_layer=2,
                    n_head=4, dtype="float32")
    model = GPT(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return model, params


# sequential ``generate()``, one program a model (``serving_helpers.py``)
sequential_reference = sequential_tokens


def test_continuous_batching_token_identical(tiny_model):
    """>= 8 concurrent requests, staggered arrival, mixed prompt/output
    lengths: greedy outputs identical to sequential generate(), with ONE
    compiled program (decode rows and the prompt chunk share a trace)."""
    model, params = tiny_model
    scfg = DeepSpeedServingConfig(block_size=8, num_blocks=128,
                                  max_batch_size=8, prefill_chunk=16,
                                  dtype="float32")
    eng = ServingEngine(model, config=scfg, params=params)

    rng = np.random.default_rng(0)
    lens = [3, 9, 17, 30, 5, 21, 12, 40, 7, 26]
    mnts = [8, 12, 5, 7, 10, 6, 15, 4, 9, 11]
    prompts = [list(rng.integers(1, 128, size=n)) for n in lens]

    futs = [eng.submit(p, max_new_tokens=m)
            for p, m in zip(prompts[:6], mnts[:6])]
    for _ in range(3):                       # staggered arrival mid-flight
        eng.step()
    futs += [eng.submit(p, max_new_tokens=m)
             for p, m in zip(prompts[6:], mnts[6:])]
    assert len(eng.sched.active) + len(eng.sched.waiting) >= 8
    eng.run()

    for p, m, f in zip(prompts, mnts, futs):
        assert f.done
        assert f.token_ids == sequential_reference(model, params, p, m)
    assert eng.compiled_programs() == 1
    assert eng.sched.stats()["finished"] == len(futs)
    eng.alloc.check_consistent()


def test_eviction_recompute_token_identical(tiny_model):
    """Cumulative KV footprint ~5x the arena: sequences are preempted,
    evicted, recomputed — and the token streams still match sequential
    generate() exactly."""
    model, params = tiny_model
    scfg = DeepSpeedServingConfig(block_size=4, num_blocks=10,   # 36 tokens
                                  max_batch_size=4, prefill_chunk=8,
                                  max_blocks_per_seq=9, dtype="float32")
    eng = ServingEngine(model, config=scfg, params=params)

    rng = np.random.default_rng(1)
    lens = (10, 14, 6, 12, 9, 16)
    mnts = (20, 16, 24, 12, 18, 14)
    prompts = [list(rng.integers(1, 128, size=n)) for n in lens]
    cumulative = sum(l + m for l, m in zip(lens, mnts))
    assert cumulative > 4 * (scfg.num_blocks - 1) * scfg.block_size

    futs = [eng.submit(p, max_new_tokens=m) for p, m in zip(prompts, mnts)]
    eng.run()

    assert eng.sched.preemption_count > 0, "arena pressure must preempt"
    assert eng.alloc.eviction_count > 0
    for p, m, f in zip(prompts, mnts, futs):
        assert f.token_ids == sequential_reference(model, params, p, m)
    assert eng.compiled_programs() == 1
    eng.alloc.check_consistent()


def test_eos_stops_early(tiny_model):
    model, params = tiny_model
    prompt = [5, 17, 3]
    ref = sequential_reference(model, params, prompt, 16)
    eos = ref[2]                                 # force a mid-stream stop
    scfg = DeepSpeedServingConfig(block_size=8, num_blocks=32,
                                  max_batch_size=2, prefill_chunk=8,
                                  dtype="float32", eos_token_id=int(eos))
    eng = ServingEngine(model, config=scfg, params=params)
    out = eng.submit(prompt, max_new_tokens=16).result()
    # identical stream, cut at the first eos (inclusive) — the tiny model
    # may emit eos earlier than the index we sampled it from
    assert out == ref[:ref.index(eos) + 1] and out[-1] == eos


def test_serving_telemetry_records(tiny_model):
    model, params = tiny_model
    ring = RingBufferSink(capacity=4096)
    hub = TelemetryHub(sinks=[ring], flush_every=0)
    scfg = DeepSpeedServingConfig(block_size=4, num_blocks=10,
                                  max_batch_size=4, prefill_chunk=8,
                                  max_blocks_per_seq=9, dtype="float32",
                                  telemetry_every=2)
    eng = ServingEngine(model, config=scfg, params=params, telemetry=hub)
    rng = np.random.default_rng(2)
    futs = [eng.submit(list(rng.integers(1, 128, size=n)), max_new_tokens=12)
            for n in (8, 20, 14, 11)]
    eng.run()
    hub.flush()

    finished = [r for r in ring.of_kind("serve_request")
                if r.get("event") == "finished"]
    assert len(finished) == len(futs)
    for rec in finished:
        assert rec["ttft_ms"] >= 0 and rec["latency_ms"] >= rec["ttft_ms"]
        assert rec["new_tokens"] == 12
    gauges = ring.of_kind("serve_step")
    assert gauges and all("queue_depth" in g and "blocks_in_use" in g
                          for g in gauges)
    if eng.sched.preemption_count:
        assert ring.of_kind("serve_preempt")


def test_init_serving_config_path(tiny_model):
    """The nested ``{"serving": {...}}`` form must NOT swallow engine
    kwargs: the engine has to serve the trained params passed alongside
    it.  Params come from a non-default seed here — with seed-0 params the
    old collapse-after-merge bug was invisible, because the silently
    re-initialized model happened to equal the fixture."""
    import deepspeed_tpu
    model, _ = tiny_model
    params = model.init_params(jax.random.PRNGKey(42))
    eng = deepspeed_tpu.init_serving(
        model=model,
        config={"serving": {"block_size": 8, "num_blocks": 32,
                            "max_batch_size": 2, "prefill_chunk": 8,
                            "dtype": "float32"}},
        params=params)
    assert isinstance(eng, ServingEngine)
    assert eng._config.block_size == 8 and eng._config.max_batch_size == 2
    out = eng.submit([1, 2, 3], max_new_tokens=4).result()
    assert out == sequential_reference(model, params, [1, 2, 3], 4)
    # explicit kwargs also override keys inside the nested dict
    eng2 = deepspeed_tpu.init_serving(
        model=model, config={"serving": {"block_size": 8, "num_blocks": 32,
                                         "max_batch_size": 2,
                                         "dtype": "float32"}},
        params=params, max_batch_size=4)
    assert eng2._config.max_batch_size == 4


def test_serving_config_in_ds_config():
    from deepspeed_tpu.runtime.config import DeepSpeedConfig
    cfg = DeepSpeedConfig({"train_batch_size": 8,
                           "serving": {"enabled": True, "block_size": 32}})
    assert cfg.serving_config.enabled and cfg.serving_config.block_size == 32


def test_submit_rejects_oversized_and_sampled(tiny_model):
    model, params = tiny_model
    scfg = DeepSpeedServingConfig(block_size=4, num_blocks=6,
                                  max_batch_size=2, dtype="float32")
    eng = ServingEngine(model, config=scfg, params=params)
    from deepspeed_tpu.serving import ArenaExhausted
    with pytest.raises(ArenaExhausted):
        eng.submit(list(range(1, 30)), max_new_tokens=20)
    with pytest.raises(ValueError):
        eng.submit([1, 2], max_new_tokens=1000)      # past n_positions
    with pytest.raises(NotImplementedError):
        eng.submit([1, 2], max_new_tokens=4, temperature=0.7)
    with pytest.raises(ValueError):
        # a typo'd SLO class must fail fast, not silently demote the
        # request to 'standard' priority
        eng.submit([1, 2], max_new_tokens=4, slo="rt")


@pytest.mark.parametrize("n_head,tiled", [(12, True), (25, False)])
def test_step_stats_say_which_paged_kernel(kernels, n_head, tiled):
    """``step()``'s stats carry ``paged_tile_pages``: the pages per tile of
    the paged kernel the decode program was built with (forced through the
    interpreter here, at GPT-2's 12 heads of 64 and 16-row pages), 0 where
    ``paged_attention`` takes the einsum (gpt2-xl's 25 heads are 1600
    lanes).  Static per engine, and the tokens are the dense path's."""
    kernels("paged_attention")
    cfg = GPTConfig(vocab_size=128, n_positions=256, n_embd=64 * n_head,
                    n_layer=1, n_head=n_head, dtype="float32")
    model = GPT(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    scfg = DeepSpeedServingConfig(block_size=16, num_blocks=40,
                                  max_batch_size=2, prefill_chunk=16,
                                  dtype="float32")
    eng = ServingEngine(model, config=scfg, params=params)
    assert eng.paged_tile_pages == (8 if tiled else 0)
    prompt = list(np.random.default_rng(0).integers(1, 128, size=20))
    fut = eng.submit(prompt, max_new_tokens=3)
    seen = set()
    while eng.sched.has_work:
        seen.add(eng.step()["paged_tile_pages"])
    assert seen == {eng.paged_tile_pages}
    assert fut.token_ids == sequential_reference(model, params, prompt, 3)
