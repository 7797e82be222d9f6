"""Top-level package surface parity (reference ``deepspeed/__init__.py``
exports): a reference user's ``deepspeed.X`` names must resolve."""

import argparse

import pytest

import deepspeed_tpu as deepspeed


@pytest.mark.parametrize("name", [
    "initialize", "init_inference", "add_config_arguments", "init_distributed",
    "zero", "DeepSpeedConfig", "log_dist",
    "DeepSpeedEngine", "PipelineEngine", "PipelineModule",
    "InferenceEngine", "DeepSpeedInferenceConfig", "DeepSpeedConfigError",
    "DeepSpeedTransformerLayer", "DeepSpeedTransformerConfig",
    "OnDevice", "add_tuning_arguments", "checkpointing",
    "module_inject", "ops",
])
def test_reference_export_resolves(name):
    assert getattr(deepspeed, name) is not None


def test_checkpointing_namespace_matches_reference():
    # deepspeed.checkpointing.configure/checkpoint are the reference API
    assert callable(deepspeed.checkpointing.configure)
    assert callable(deepspeed.checkpointing.checkpoint)


def test_add_tuning_arguments_parses():
    p = deepspeed.add_tuning_arguments(argparse.ArgumentParser())
    a = p.parse_args(["--lr_schedule", "WarmupLR", "--warmup_num_steps", "7"])
    assert a.lr_schedule == "WarmupLR" and a.warmup_num_steps == 7


def test_dir_lists_lazy_exports():
    names = dir(deepspeed)
    assert "DeepSpeedEngine" in names and "InferenceEngine" in names


def test_bool_flags_honor_false():
    p = deepspeed.add_tuning_arguments(argparse.ArgumentParser())
    a = p.parse_args(["--lr_range_test_staircase", "False"])
    assert a.lr_range_test_staircase is False


def test_unknown_attribute_raises():
    with pytest.raises(AttributeError):
        deepspeed.definitely_not_an_export


@pytest.mark.parametrize("source", ["__init__.py", "models/gpt.py"])
def test_the_train_path_imports_no_serving_code(source):
    """What ``import deepspeed_tpu`` and ``from deepspeed_tpu.models import
    gpt`` load is what a train cell's ``setup_s`` pays for: the paged kernels,
    the hybrid walk and the serving engine are imported inside the functions
    that serve (read from the source: an import of the package costs seconds
    here)."""
    import ast
    import os
    tree = ast.parse(open(os.path.join(os.path.dirname(deepspeed.__file__), source)).read())
    # module level: not inside a function or a class, whatever else nests it
    level = list(tree.body)
    for node in level:
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
            level.extend(ast.iter_child_nodes(node))
    names = set()
    for node in level:
        if isinstance(node, ast.Import):
            names.update(alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom):
            names.update(f"{node.module}.{alias.name}" for alias in node.names)
    assert any(name.startswith("deepspeed_tpu.") for name in names)
    lazy = ("ops.pallas.decode_attention", "models.hybrid", "serving")
    assert not [name for name in names if any(
        f".{part}." in f".{name}." for part in lazy)], names
