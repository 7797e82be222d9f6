"""``tools/stack_copies.py``'s reading of a compiled program's text, on a
few lines written by hand in the compiler's form (the tool's compiles of whole
steps are ``tests/unit/ops/test_chip_compile.py``'s)."""

import pytest

from tools import stack_copies as sc

TEXT = """HloModule jit_step, is_scheduled=true

%fused_computation.1 (param_0.1: bf16[5,1536,24576]) -> bf16[5,1536,24576] {
  %param_0.1 = bf16[5,1536,24576]{2,1,0:T(8,128)(2,1)} parameter(0)
  ROOT %copy.9 = bf16[5,1536,24576]{1,2,0:T(8,128)(2,1)} copy(%param_0.1)
}

%region_0.20 (arg: (bf16[5,1536,24576], s32[])) -> bf16[12,24576] {
  %copy.584 = bf16[5,1536,24576]{1,2,0:T(8,128)(2,1)} copy(%get-tuple-element.4197), backend_config={"window_config":{"estimated_cycles":"2907648"}}
  %fusion.3 = bf16[5,1536,24576]{1,2,0:T(8,128)(2,1)} fusion(%copy.584), kind=kLoop, calls=%fused_computation.1
  ROOT %slice.1 = bf16[1,32768,512]{2,1,0:T(8,128)(2,1)} slice(%get-tuple-element.21), slice={[0:1], [0:32768], [0:512]}
}

ENTRY %main.303 (a: bf16[5,1536,24576]) -> bf16[524,1,16256] {
  %copy.601 = bf16[128,192,1536]{2,0,1:T(8,128)(2,1)} copy(%bitcast.2), backend_config={"window_config":{"estimated_cycles":"4300"}}
  %copy.7 = f32[524,1,16256]{2,1,0:T(8,128)} copy(%pad_convert_fusion), backend_config={"window_config":{"estimated_cycles":"818446"}}
  %copy.8 = pred[12]{0} copy(%p)
}
"""
LEAVES = {"q_b_w": ("bf16", (5, 1536, 24576)), "kv_b_t": ("bf16", (5, 32768, 512)),
          "lnf_g": ("bf16", (7168,))}


def test_a_fusions_body_is_the_fusions_and_the_cycles_are_the_compilers():
    found = {i["name"]: i for i in sc.instructions(TEXT)}
    assert "copy.9" not in found and "param_0.1" not in found       # the fusion's own
    assert found["copy.584"]["cycles"] == 2907648 and found["fusion.3"]["cycles"] == 0
    assert found["copy.584"]["computation"] == "region_0.20"
    assert found["copy.601"]["computation"] == "main.303"
    assert found["slice.1"]["shape"] == (1, 32768, 512) and found["slice.1"]["op"] == "slice"
    assert found["copy.584"]["layout"].startswith("{1,2,0")


@pytest.mark.parametrize("made, dtype, want", [
    ((5, 1536, 24576), "bf16", ("q_b_w", "stack")),
    ((5, 24576, 1536), "bf16", ("q_b_w", "stack")),          # transposed
    ((1, 1536, 24576), "bf16", ("q_b_w", "layer")),
    ((128, 192, 1536), "bf16", ("q_b_w", "layer")),          # its lanes cut by head
    ((512, 128, 256), "bf16", ("kv_b_t", "layer")),
    ((5, 1536, 24576), "f32", None),                         # another type
    ((524, 1, 16256), "bf16", None),                         # an activation
    ((7168,), "bf16", None),                                 # a vector is no stack
], ids=["stack", "transposed", "layer", "by_head", "by_head_t", "dtype", "activation",
        "vector"])
def test_a_result_is_placed_on_the_leaf_it_holds(made, dtype, want):
    assert sc.placed({"shape": made, "dtype": dtype}, LEAVES) == want


def test_the_copies_come_costliest_first_with_their_bytes_and_their_leaf():
    copies = sc.stack_copies(TEXT, LEAVES)
    assert [c["name"] for c in copies] == ["copy.584", "copy.7", "copy.601"]   # 12 B left out
    assert [c["bytes"] for c in copies] == [5 * 1536 * 24576 * 2, 524 * 16256 * 4,
                                            128 * 192 * 1536 * 2]
    assert [c["placed"] for c in copies] == [("q_b_w", "stack"), None, ("q_b_w", "layer")]
    moved = sc.stack_copies(TEXT, LEAVES, ops=("copy", "fusion", "slice"))
    assert {c["name"]: c["placed"] for c in moved if c["op"] != "copy"} == {
        "fusion.3": ("q_b_w", "stack"), "slice.1": ("kv_b_t", "layer")}
