"""Unit coverage for the closed loop's pieces: the typed search space
(guards + dedup + env/config patch split), the analytic pruner (same
arithmetic as the offload budget gate), the retune fingerprint policies
(off/warn/refuse), ``better()`` ranking semantics, and the emitted
manifest / ``ds_config_patch.json`` artifact shapes."""

import json
import os

import pytest

from deepspeed_tpu.autotuning import fingerprint as fp_mod
from deepspeed_tpu.autotuning.fingerprint import (PATCH_BASENAME,
                                                  StaleTuningError,
                                                  environment_fingerprint,
                                                  fingerprint_digest)
from deepspeed_tpu.autotuning.loop import (MANIFEST_BASENAME,
                                           ClosedLoopAutotuner)
from deepspeed_tpu.autotuning.scheduler import SCORED, TrialResult
from deepspeed_tpu.autotuning.scoring import TrialScore, better
from deepspeed_tpu.autotuning.space import (SearchSpace, UnknownKnobError,
                                            apply_patch, patch_diff)
from deepspeed_tpu.runtime import memory_model


class TestSearchSpace:
    def test_guard_collapses_dependent_knobs(self):
        """qwz rides only on stage 3, so {stage x micro x qwz} is 2*2*2=8
        raw combos but the stage-1 half collapses to 2*1 = dedup to 6."""
        cands = SearchSpace({"zero_stage": (1, 3), "micro_batch": (1, 4),
                             "qwz": (False, True)}).enumerate()
        assert len(cands) == 6
        for c in cands:
            if c.knobs.get("zero_stage") == 1:
                assert "qwz" not in c.knobs
        # False values survive (only None is dropped)
        assert any(c.knobs.get("qwz") is False for c in cands)

    def test_unknown_knob_fails_loudly(self):
        with pytest.raises(UnknownKnobError, match="zero_stag"):
            SearchSpace({"zero_stag": (1, 3)})
        with pytest.raises(UnknownKnobError, match="no values"):
            SearchSpace({"zero_stage": ()})

    def test_apply_patch_and_diff(self):
        base = {"train_micro_batch_size_per_gpu": 1,
                "zero_optimization": {"stage": 1}}
        patch = {"zero_optimization.stage": 3,
                 "train_micro_batch_size_per_gpu": 4,
                 "zero_optimization.prefetch_depth": 2}
        cfg = apply_patch(base, patch)
        assert cfg["zero_optimization"]["stage"] == 3
        assert cfg["train_micro_batch_size_per_gpu"] == 4
        assert cfg["zero_optimization"]["prefetch_depth"] == 2
        assert base["zero_optimization"]["stage"] == 1  # base untouched
        diff = patch_diff(base, patch)
        assert diff["zero_optimization.stage"] == {"from": 1, "to": 3}
        assert diff["zero_optimization.prefetch_depth"] == {"from": None,
                                                            "to": 2}

    def test_mesh_knob_replaces_whole_dict(self):
        cfg = apply_patch({"mesh": {"data": 8}}, {"mesh": {"data": 4,
                                                           "model": 2}})
        assert cfg["mesh"] == {"data": 4, "model": 2}


class TestBetter:
    def _score(self, gf, mfu=0.2, step=1.0, ok=True):
        return TrialScore(goodput_frac=gf, mfu=mfu, step_time_s=step,
                          wall_s=4.0, steps=4, productive_steps=4,
                          conservation_ok=ok)

    def test_goodput_dominates(self):
        assert better(self._score(0.9, mfu=0.1), self._score(0.8, mfu=0.9))

    def test_mfu_then_step_time_break_ties(self):
        assert better(self._score(0.9, mfu=0.3), self._score(0.9, mfu=0.2))
        assert better(self._score(0.9, step=0.5), self._score(0.9, step=1.0))

    def test_nonconserving_never_wins(self):
        assert not better(self._score(0.99, ok=False), self._score(0.5))
        assert better(self._score(0.5), self._score(0.99, ok=False))
        assert not better(None, self._score(0.1))
        assert better(self._score(0.1), None)


class TestAnalyticPruning:
    """prune_reason uses the SAME memory model the engine's budget gate
    enforces — these pin the decision boundary on both sides."""

    def _loop(self, tmp_path, budget, stage_values=(1, 3), **model_info):
        info = {"num_params": 100_000_000, "block_params": 7_000_000,
                "n_layer": 12}
        info.update(model_info)
        cfg = {"mesh": {"data": 8},
               "autotuning": {"search_space": {"zero_stage": stage_values},
                              "model_info": info,
                              "device_memory_bytes": budget,
                              "results_dir": str(tmp_path / "r")}}
        return ClosedLoopAutotuner(cfg)

    def test_stage_state_boundary_exact(self, tmp_path):
        """A budget of exactly the stage-1 state runs; one byte less
        prunes — prune_reason agrees with stage_state_bytes to the byte."""
        p, world = 100_000_000, 8
        need = memory_model.stage_state_bytes(p, 1, world)
        loop = self._loop(tmp_path, need, stage_values=(1,))
        (cand,) = loop.space.enumerate()
        assert loop.prune_reason(cand) is None
        loop_tight = self._loop(tmp_path, need - 1, stage_values=(1,))
        reason = loop_tight.prune_reason(cand)
        assert reason is not None and f"{need} B" in reason

    def test_stage3_uses_step_peaks(self, tmp_path):
        p, world = 100_000_000, 8
        peaks = memory_model.analytic_step_peaks(
            p, world, block_params=7_000_000, n_layer=12)
        loop = self._loop(tmp_path, peaks.plain_peak_bytes,
                          stage_values=(3,))
        (cand,) = loop.space.enumerate()
        assert loop.prune_reason(cand) is None
        loop_tight = self._loop(tmp_path, peaks.plain_peak_bytes - 1,
                                stage_values=(3,))
        assert "gathered peak" in loop_tight.prune_reason(cand)

    def test_offload_param_unlocks_the_window(self, tmp_path):
        """With offload_param the window peak (not the gathered peak) is
        what must fit — the same candidate flips from pruned to runnable."""
        p, world = 100_000_000, 8
        peaks = memory_model.analytic_step_peaks(
            p, world, block_params=7_000_000, n_layer=12)
        budget = peaks.window_peak_bytes      # < plain_peak_bytes
        cfg = {"mesh": {"data": world},
               "autotuning": {
                   "search_space": {"zero_stage": (3,),
                                    "offload_param": (None, "cpu")},
                   "model_info": {"num_params": p,
                                  "block_params": 7_000_000, "n_layer": 12},
                   "device_memory_bytes": budget,
                   "results_dir": str(tmp_path / "r")}}
        loop = ClosedLoopAutotuner(cfg)
        cands = loop.space.enumerate()
        by_offload = {c.knobs.get("offload_param"): c for c in cands}
        assert loop.prune_reason(by_offload["cpu"]) is None
        assert "gathered peak" in loop.prune_reason(by_offload[None])

    def test_no_budget_means_no_pruning(self, tmp_path):
        loop = self._loop(tmp_path, budget=0)
        for cand in loop.space.enumerate():
            assert loop.prune_reason(cand) is None


class TestFingerprint:
    def _fp(self, **overrides):
        fp = environment_fingerprint(mesh_shape={"data": 8},
                                     model_dims={"num_params": 1000})
        fp.update(overrides)
        return fp

    def test_intersection_only_compare(self):
        stored = self._fp()
        current = self._fp()
        del current["model"]["num_params"]     # leaner consumer
        assert fp_mod.compare(stored, current) == []
        current = self._fp()
        current["model"]["num_params"] = 2000
        (m,) = fp_mod.compare(stored, current)
        assert "num_params" in m and "1000" in m and "2000" in m

    def test_policies(self, tmp_path):
        stored = self._fp()
        doc = {"fingerprint": stored, "patch": {}}
        current = self._fp()
        current["pod"]["device_count"] = 4096
        assert fp_mod.check(doc, current, policy="off") == []
        mismatches = fp_mod.check(doc, current, policy="warn")
        assert any("device_count" in m for m in mismatches)
        with pytest.raises(StaleTuningError, match="device_count"):
            fp_mod.check(doc, current, policy="refuse")
        # matching fingerprint never raises, even under refuse
        assert fp_mod.check(doc, stored, policy="refuse") == []

    def test_missing_artifact_warns_never_refuses(self, tmp_path):
        missing = str(tmp_path / "nope" / PATCH_BASENAME)
        assert fp_mod.check(missing, self._fp(), policy="refuse") == []

    def test_digest_is_stable_and_sensitive(self):
        a, b = self._fp(), self._fp()
        assert fingerprint_digest(a) == fingerprint_digest(b)
        b["model"]["num_params"] = 1001
        assert fingerprint_digest(a) != fingerprint_digest(b)


class TestArtifacts:
    def _winner(self):
        score = TrialScore(goodput_frac=0.91, mfu=0.2, step_time_s=0.5,
                           wall_s=2.0, steps=4, productive_steps=4,
                           conservation_ok=True)
        return TrialResult(name="c0001", status=SCORED,
                           patch={"zero_optimization.stage": 3},
                           knobs={"zero_stage": 3}, rc=0, score=score,
                           trial_dir="/tmp/t/c0001")

    def test_manifest_and_patch_shape(self, tmp_path):
        cfg = {"zero_optimization": {"stage": 1},
               "autotuning": {"search_space": {"zero_stage": (1, 3)},
                              "results_dir": str(tmp_path)}}
        loop = ClosedLoopAutotuner(
            cfg, fingerprint={"schema": 1, "pod": {"device_count": 8}})
        loop.trials = [self._winner()]
        loop.best = loop.trials[0]
        paths = loop.write_artifacts()

        man = json.load(open(paths["manifest"]))
        assert os.path.basename(paths["manifest"]) == MANIFEST_BASENAME
        assert man["counts"] == {"candidates": 1, "pruned": 0, "run": 1,
                                 "scored": 1, "degraded": 0}
        assert man["best"]["name"] == "c0001"
        assert man["fingerprint_digest"] == fingerprint_digest(
            man["fingerprint"])

        patch = json.load(open(paths["patch"]))
        assert os.path.basename(paths["patch"]) == PATCH_BASENAME
        assert patch["patch"] == {"zero_optimization.stage": 3}
        assert patch["diff"]["zero_optimization.stage"] == {"from": 1,
                                                            "to": 3}
        assert patch["score"]["goodput_frac"] == pytest.approx(0.91)
        assert patch["provenance"]["trial"] == "c0001"
        assert patch["provenance"]["manifest"] == paths["manifest"]

    def test_no_winner_emits_manifest_only(self, tmp_path):
        cfg = {"autotuning": {"search_space": {"zero_stage": (1,)},
                              "results_dir": str(tmp_path)}}
        loop = ClosedLoopAutotuner(cfg, fingerprint={"schema": 1})
        paths = loop.write_artifacts()
        assert "patch" in paths or not os.path.exists(
            os.path.join(str(tmp_path), PATCH_BASENAME))
        assert "patch" not in paths
        assert json.load(open(paths["manifest"]))["best"] is None
