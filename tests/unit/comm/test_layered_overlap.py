"""Layered ZeRO-3 (overlap_comm): layered-vs-bulk bitwise parity across
the compression variants, no-retrace program caching, a traced run's
timeline through ``tools/trace_merge.py`` (measured spans only),
the comms-logger byte-table staleness regression, and the static
whole-tree-gather lint."""

import importlib.util
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models.gpt import GPT, GPTConfig

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO_ROOT, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod

# shapes chosen so every sharded per-layer shard slice is a multiple of
# the 256-element quantization block (layer-major flattening makes
# per-slice == stacked blockwise quantization only then)
CFG = dict(vocab_size=128, n_positions=32, n_embd=64, n_layer=4, n_head=4,
           dtype=jnp.float32, attn_impl="reference")

IDS = np.random.default_rng(0).integers(0, 128, (8, 32)).astype(np.int32)


def _engine(telemetry=None, **zero_over):
    model = GPT(GPTConfig(**CFG))
    config = {"train_batch_size": 8,
              "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
              "comms_logger": {"enabled": True},
              "zero_optimization": {"stage": 3, **zero_over}}
    if telemetry:
        config["telemetry"] = telemetry
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=model.init_params(jax.random.key(0)),
        config=config, seed=7)
    return engine


def _force_bulk(engine):
    """Same compressed-collective config, bulk (whole-tree) schedule —
    the parity comparator.  ``exact_only`` is cleared so the exact
    variant runs the bulk cc step instead of falling back to the
    standard XLA program (whose reduction order differs in fp32)."""
    engine._cc["layered"] = False
    engine._cc["exact_only"] = False
    return engine


def _steps(engine, n=2, micros=1):
    out = []
    for _ in range(n):
        for _ in range(micros):
            loss = engine.forward(IDS, IDS)
            engine.backward(loss)
        grads = jax.device_get(engine.state.grad_acc)
        engine.step()
        out.append((float(np.asarray(loss)), grads))
    return out


VARIANTS = {
    "exact": {},
    "qwz_int8": {"zero_quantized_weights": True},
    "qgz": {"zero_quantized_gradients": True},
    "hpz": {"zero_quantized_weights": True, "zero_quantized_gradients": True,
            "zero_hpz_partition_size": 4},
}


class TestLayeredBulkParity:
    @pytest.mark.parametrize("variant", sorted(VARIANTS))
    def test_loss_and_grads_bitwise_equal(self, variant):
        over = VARIANTS[variant]
        # micros=2 on hpZ exercises the secondary refresh AND reuse steps
        micros = 2 if "zero_hpz_partition_size" in over else 1

        layered = _engine(overlap_comm=True, **over)
        r_lay = _steps(layered, micros=micros)
        assert layered._cc["layered"] is True, layered._cc
        assert layered._cc["n_layer"] == CFG["n_layer"]

        bulk = _force_bulk(_engine(overlap_comm=True, **over))
        r_bulk = _steps(bulk, micros=micros)

        for (l_lay, g_lay), (l_bulk, g_bulk) in zip(r_lay, r_bulk):
            assert l_lay == l_bulk   # fp32, bitwise
            leaves_lay = jax.tree.leaves(g_lay)
            leaves_bulk = jax.tree.leaves(g_bulk)
            assert len(leaves_lay) == len(leaves_bulk)
            for a, b in zip(leaves_lay, leaves_bulk):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_no_retrace_across_steps(self):
        engine = _engine(overlap_comm=True)
        _steps(engine, n=3)
        # one compiled program serves every step: a shape/dtype leak in
        # the scan carry or prefetch ring would retrace per call
        assert engine._layered_step._cache_size() == 1

    def test_non_scan_model_falls_back(self):
        from deepspeed_tpu.models.simple import SimpleModel, random_dataset
        model = SimpleModel(hidden_dim=64, nlayers=2)
        params = model.init_params(jax.random.PRNGKey(0), batch_size=2)
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model, model_parameters=params, seed=7,
            config={"train_batch_size": 8,
                    "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                    "zero_optimization": {"stage": 3, "overlap_comm": True}})
        data = random_dataset(8, 64, seed=7)
        xs = np.stack([d[0] for d in data])
        ys = np.stack([d[1] for d in data])
        loss = engine.forward(xs, ys)
        engine.backward(loss)
        engine.step()
        assert np.isfinite(float(np.asarray(loss)))
        # overlap requested but the model can't run layered: the engine
        # must fall back (standard program for exact-only) — not crash
        assert engine._cc["layered"] is False


class TestTraceHoldsOnlyWhatWasMeasured:
    """The engine used to stamp ``zero3.comm``/``zero3.compute`` lanes, "the
    schedule the program structure admits" (overlap L/(L+2)), into the
    measured fwd window, and ``trace_merge`` read an overlap fraction back
    off them.  What overlaps is measured on the device now (the benchmark's
    ``collective_exposed_pct.train``); a traced run's timeline carries the
    spans the host really opened and nothing invented."""

    def test_traced_run_has_its_spans_and_no_invented_lanes(self, tmp_path):
        for tag, zero_over in (
                ("layered", {"overlap_comm": True}),
                ("bulk", {"overlap_comm": False,
                          "zero_quantized_weights": True})):
            td = tmp_path / tag
            td.mkdir()
            engine = _engine(
                telemetry={"enabled": True, "tracing": True,
                           "trace_dir": str(td),
                           "jsonl_path": str(td / "run.jsonl"),
                           "watchdog_enabled": False},
                **zero_over)
            _steps(engine, n=1)
            engine.telemetry_close()
            merge_main = _load_tool("trace_merge").main
            merged_path = str(td / "merged.json")
            assert merge_main([str(td / "trace_rank0.json"), "-o", merged_path,
                               "--flops", str(td / "run.jsonl")]) == 0
            with open(merged_path) as f:
                merged = json.load(f)
            names = {ev["name"] for ev in merged["traceEvents"]
                     if ev.get("ph") == "X"}
            assert {"fwd", "bwd", "step"} <= names
            assert not any(n.startswith(("zero3.", "offload.stage"))
                           for n in names), names
            assert "overlap" not in merged["metadata"]


class TestByteTableTracksConfig:
    """Regression for the stale ``_cc_bytes_reuse``/``_cc_bytes_refresh``
    caches: per-step comms-logger bytes must follow the ACTIVE config
    after a compression reconfig or a layered<->bulk flip, not the first
    table ever computed."""

    @staticmethod
    def _op_bytes(engine, op):
        ops = engine.comms_logger.summary()["ops"]
        return ops[op]["total_bytes"] if op in ops else 0

    def test_bits_reconfig_changes_logged_bytes(self):
        engine = _engine(zero_quantized_weights=True)
        _steps(engine, n=1)
        first = self._op_bytes(engine, "qwz_all_gather")
        assert first > 0
        # reconfigure compression (int8 -> int4) mid-run and invalidate:
        # the rebuilt programs AND the logged bytes must both follow
        engine._cc["qw_bits"] = 4
        engine._invalidate_loss_programs()
        assert engine._cc_bytes_tables == {}
        _steps(engine, n=1)
        second = self._op_bytes(engine, "qwz_all_gather") - first
        assert second != first
        fresh = engine._cc_byte_table(reuse=False)["qwz_all_gather"][0]
        assert second == fresh

    def test_layered_and_bulk_use_distinct_tables(self):
        engine = _engine(overlap_comm=True, zero_quantized_weights=True)
        _steps(engine, n=1)
        layered_step = self._op_bytes(engine, "qwz_all_gather")
        _force_bulk(engine)
        engine._invalidate_loss_programs()
        _steps(engine, n=1)
        bulk_step = self._op_bytes(engine, "qwz_all_gather") - layered_step
        # layered moves (L + depth)/L times the block-leaf bytes of bulk
        assert layered_step > bulk_step > 0
        assert layered_step == engine._cc_byte_table(
            reuse=False, layered=True)["qwz_all_gather"][0]
        assert bulk_step == engine._cc_byte_table(
            reuse=False, layered=False)["qwz_all_gather"][0]

    def test_apply_program_invalidation_clears_tables(self):
        engine = _engine(zero_quantized_weights=True)
        _steps(engine, n=1)
        assert engine._cc_bytes_tables
        engine._invalidate_apply_programs()
        assert engine._cc_bytes_tables == {}


def test_overlap_structure_lint_clean():
    """The AST lint guarding the layered step against whole-tree gathers
    must hold on the tree as committed (and run from the suite, so a
    regression fails CI, not just the standalone tool)."""
    assert _load_tool("check_overlap_structure").check_files() == []
