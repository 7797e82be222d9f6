"""End-to-end acceptance for tiered KV + prefix cache.

The contract stays the one from ``test_serving_e2e.py``: tiering is an
*engine-side* optimization, so under an arena a fraction of the working
set — with sequences spilled to host/NVMe and restored, and prompt blocks
shared through the prefix cache — greedy outputs must be token-identical
to sequential ``generate()``.  Restore is bitwise (CRC-framed chunks), so
this holds exactly, not approximately.
"""

import numpy as np
import pytest

import jax

from deepspeed_tpu.models.gpt import GPT, GPTConfig
from deepspeed_tpu.serving import DeepSpeedServingConfig, ServingEngine
from deepspeed_tpu.telemetry.hub import RingBufferSink, TelemetryHub


@pytest.fixture(scope="module")
def tiny_model():
    cfg = GPTConfig(vocab_size=128, n_positions=128, n_embd=32, n_layer=2,
                    n_head=4, dtype="float32")
    model = GPT(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return model, params


def sequential_reference(model, params, prompt, n_new):
    out = model.generate(params, np.asarray(prompt, np.int32)[None], n_new)
    return list(np.asarray(out)[0, len(prompt):])


def shared_prompt_workload(seed=7):
    """Six prompts sharing a 32-token system prefix, mixed tails/outputs."""
    rng = np.random.default_rng(seed)
    system = list(rng.integers(1, 128, size=32))
    tails = (3, 7, 5, 9, 4, 6)
    mnts = (12, 10, 14, 8, 12, 10)
    prompts = [system + list(rng.integers(1, 128, size=t)) for t in tails]
    return system, prompts, mnts


def test_tiered_spill_restage_prefix_token_identical(tiny_model, tmp_path):
    """The PR's acceptance bar: arena sized to a fraction of the working
    set, a one-block host cache forcing a full NVMe round trip, prefix
    sharing of the system prompt — and every token stream still matches
    the unconstrained sequential baseline, with the spill, the NVMe
    restage, and the prefix hits asserted from telemetry."""
    model, params = tiny_model
    system, prompts, mnts = shared_prompt_workload()
    demand = sum(len(p) + m for p, m in zip(prompts, mnts))

    ring = RingBufferSink(capacity=8192)
    hub = TelemetryHub(sinks=[ring], flush_every=0)
    scfg = DeepSpeedServingConfig(
        block_size=4, num_blocks=15, max_batch_size=4, prefill_chunk=8,
        max_blocks_per_seq=16, dtype="float32", telemetry_every=1,
        kv_tiering=True, kv_offload_dir=str(tmp_path / "kv"),
        kv_host_cache_bytes=1024,            # < one block: spills go to NVMe
        prefix_cache=True)
    assert demand > 4 * (scfg.num_blocks - 1) * scfg.block_size

    eng = ServingEngine(model, config=scfg, params=params, telemetry=hub)
    try:
        futs = [eng.submit(p, max_new_tokens=m)
                for p, m in zip(prompts[:3], mnts[:3])]
        for _ in range(4):                   # staggered arrival mid-flight
            eng.step()
        futs += [eng.submit(p, max_new_tokens=m)
                 for p, m in zip(prompts[3:], mnts[3:])]
        eng.run()                            # must not raise ArenaExhausted
        hub.flush()

        for p, m, f in zip(prompts, mnts, futs):
            assert f.done
            assert f.token_ids == sequential_reference(model, params, p, m)

        spills = ring.of_kind("kv_spill")
        restages = [r for r in ring.of_kind("kv_restage") if r["ok"]]
        prefix_hits = ring.of_kind("prefix_hit")
        assert spills, "arena pressure must reach the spill rung"
        assert any(r["source"] == "nvme" for r in restages), \
            "expected at least one full NVMe round trip"
        assert prefix_hits, "shared system prompt must hit the prefix cache"
        assert all(h["tokens"] >= scfg.block_size for h in prefix_hits)
        assert eng.sched.spill_count >= 1
        assert eng.sched.restage_count >= 1
        assert eng.prefix.hits >= 1

        # tiering gather/scatter are separate jits: the serving step count
        # stays at the one program
        assert eng.compiled_programs() == 1
        eng.alloc.check_consistent()
    finally:
        eng.close()


def test_zero_spill_budget_degrades_to_recompute(tiny_model, tmp_path):
    """With the spill budget refusing everything, preemption falls back to
    the destructive evict+recompute path — still token-identical."""
    model, params = tiny_model
    rng = np.random.default_rng(9)
    lens = (10, 14, 6, 12, 9, 16)
    mnts = (20, 16, 24, 12, 18, 14)
    prompts = [list(rng.integers(1, 128, size=n)) for n in lens]
    scfg = DeepSpeedServingConfig(
        block_size=4, num_blocks=10, max_batch_size=4, prefill_chunk=8,
        max_blocks_per_seq=9, dtype="float32",
        kv_tiering=True, kv_offload_dir=str(tmp_path / "kv"),
        kv_spill_budget_bytes=1)
    eng = ServingEngine(model, config=scfg, params=params)
    try:
        futs = [eng.submit(p, max_new_tokens=m)
                for p, m in zip(prompts, mnts)]
        eng.run()
        assert eng.sched.preemption_count > 0
        assert eng.sched.spill_count == 0    # every spill was refused
        for p, m, f in zip(prompts, mnts, futs):
            assert f.token_ids == sequential_reference(model, params, p, m)
        eng.alloc.check_consistent()
    finally:
        eng.close()
