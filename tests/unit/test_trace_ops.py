"""``tools/trace_ops.py`` on a hand-made trace whose answers can be worked out
on paper (``tests/unit/data/train_step.xspace.txt``)."""

import json
import os

import pytest

from tools import trace_ops

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
US = 1e-6


@pytest.fixture(scope="module")
def xplane(tmp_path_factory):
    from jax.profiler import ProfileData
    text = open(os.path.join(DATA, "train_step.xspace.txt")).read()
    path = tmp_path_factory.mktemp("trace") / "train_step.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return str(path)


def test_a_family_is_the_ops_name_less_its_numbering():
    assert trace_ops.family("bitcast_dynamic-update-slice_fusion.12.remat3") == \
        "bitcast_dynamic-update-slice_fusion"
    assert trace_ops.family("slice_bitcast_fusion.71.clone") == "slice_bitcast_fusion"
    assert trace_ops.family("flash_fwd") == "flash_fwd"


def test_the_three_tables_are_self_time_and_sum_to_the_busy_time(xplane):
    found = trace_ops.tables(xplane)
    assert found["busy_s"] == pytest.approx(40 * US)
    assert found["window_s"] == pytest.approx(42 * US)
    want = {
        "family": {"fusion": 18, "while": 11, "bitcast_dynamic-update-slice_fusion": 6,
                   "constant_dynamic-slice_fusion": 3, "copy": 2},
        "scope": {"blocks": 20, "mlp": 8, "attn": 6, "optimizer": 4, "none": 2},
    }
    for table, rows in want.items():
        assert {k: round(v / US, 6) for k, v in found[table].items()} == rows
    stacks = {k: round(v / US, 6) for k, v in found["stack"].items()}
    # the jit(...) head is dropped, the loop's own path kept; the stash is one row
    assert stacks["jvp(blocks)/while/body/dynamic_update_slice"] == 6
    assert stacks["transpose(jvp(blocks))/while/body/dynamic_slice"] == 3
    assert stacks["jvp(blocks)/while"] == 8
    assert stacks["(no stack)"] == 2
    assert sum(stacks.values()) == 40


def test_the_command_prints_the_tables_a_step(xplane, capsys):
    with pytest.raises(SystemExit, match="no .xplane.pb under it"):
        trace_ops.main([os.path.dirname(xplane)])      # a directory is a profile's
    assert trace_ops.main([xplane, "--steps", "2", "--under", "blocks", "--json"]) == 0
    report = json.loads(capsys.readouterr().out)
    stash = next(r for r in report["family"]
                 if r["name"] == "bitcast_dynamic-update-slice_fusion")
    assert stash["share_pct"] == pytest.approx(15.0)
    assert stash["ms_per_step"] == pytest.approx(3e-3)
    assert all("blocks" in r["name"] for r in report["stack"])
    assert trace_ops.main([xplane]) == 0
    assert "by scope" in capsys.readouterr().out
