"""``tools/hlo_table.py``: what each operation of a compiled program reads and writes, from its HLO text alone
(PR 43: how PERF.md section 5's table of the latent tick was made).  A module written by hand in the optimised
text's grammar: an entry copy of a stacked weight, a layer loop that slices it into fast memory before the dot,
a second weight the dot's own fusion slices, a Pallas call."""

import importlib.util
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
spec = importlib.util.spec_from_file_location("hlo_table", os.path.join(ROOT, "tools", "hlo_table.py"))
hlo_table = importlib.util.module_from_spec(spec)
spec.loader.exec_module(hlo_table)

HLO = """HloModule jit_tick, is_scheduled=true

%fused_slice (param_0.1: bf16[4,1536,8192], param_1.1: s32[]) -> bf16[1,1536,8192] {
  %param_0.1 = bf16[4,1536,8192]{1,2,0:T(8,128)(2,1)} parameter(0)
  %param_1.1 = s32[]{:T(128)} parameter(1)
  %c.0 = s32[]{:T(128)} constant(0)
  ROOT %ds.0 = bf16[1,1536,8192]{1,2,0:T(8,128)(2,1)S(1)} dynamic-slice(%param_0.1, %param_1.1, %c.0, %c.0), dynamic_slice_sizes={1,1536,8192}
}

%nested (param_0.3: bf16[4,512,1536], param_1.3: s32[]) -> bf16[512,1536] {
  %param_0.3 = bf16[4,512,1536]{2,1,0:T(8,128)(2,1)} parameter(0)
  %param_1.3 = s32[]{:T(128)} parameter(1)
  %c.1 = s32[]{:T(128)} constant(0)
  %ds.1 = bf16[1,512,1536]{2,1,0:T(8,128)(2,1)} dynamic-slice(%param_0.3, %param_1.3, %c.1, %c.1), dynamic_slice_sizes={1,512,1536}
  ROOT %bc.1 = bf16[512,1536]{1,0:T(8,128)(2,1)} bitcast(%ds.1)
}

%fused_dot (param_0.2: bf16[8,512], param_1.2: bf16[4,512,1536], param_2.2: s32[]) -> bf16[8,1536] {
  %param_0.2 = bf16[8,512]{1,0:T(8,128)(2,1)S(1)} parameter(0)
  %param_1.2 = bf16[4,512,1536]{2,1,0:T(8,128)(2,1)} parameter(1)
  %param_2.2 = s32[]{:T(128)} parameter(2)
  %layer.2 = bf16[512,1536]{1,0:T(8,128)(2,1)} fusion(%param_1.2, %param_2.2), kind=kLoop, calls=%nested
  ROOT %dot.2 = bf16[8,1536]{1,0:T(8,128)(2,1)S(1)} convolution(%param_0.2, %layer.2), dim_labels=bf_io->bf
}

%body (arg: (s32[], bf16[8,512], bf16[4,1536,8192], bf16[4,512,1536], bf16[5,256,512,640])) -> (s32[], bf16[8,512], bf16[4,1536,8192], bf16[4,512,1536], bf16[5,256,512,640]) {
  %arg = (s32[]{:T(128)}, bf16[8,512]{1,0:T(8,128)(2,1)S(1)}, bf16[4,1536,8192]{1,2,0:T(8,128)(2,1)}, bf16[4,512,1536]{2,1,0:T(8,128)(2,1)}, bf16[5,256,512,640]{3,2,1,0:T(8,128)(2,1)}) parameter(0)
  %i = s32[]{:T(128)} get-tuple-element(%arg), index=0
  %x = bf16[8,512]{1,0:T(8,128)(2,1)S(1)} get-tuple-element(%arg), index=1
  %stack = bf16[4,1536,8192]{1,2,0:T(8,128)(2,1)} get-tuple-element(%arg), index=2
  %other = bf16[4,512,1536]{2,1,0:T(8,128)(2,1)} get-tuple-element(%arg), index=3
  %pool = bf16[5,256,512,640]{3,2,1,0:T(8,128)(2,1)} get-tuple-element(%arg), index=4
  %constant_dynamic-slice_fusion.7 = bf16[1,1536,8192]{1,2,0:T(8,128)(2,1)S(1)} fusion(%stack, %i), kind=kLoop, calls=%fused_slice, metadata={op_name="jit(tick)/while/body/closed_call/while/body/dynamic_slice" stack_frame_id=15}
  %fusion.9 = bf16[8,1536]{1,0:T(8,128)(2,1)S(1)} fusion(%x, %other, %i), kind=kOutput, calls=%fused_dot, metadata={op_name="jit(tick)/while/body/closed_call/while/body/closed_call/attn/q_down/bse,er->bsr/dot_general" stack_frame_id=3}
  %latent_decode.3 = (f32[8,128,512]{2,1,0:T(8,128)S(1)}, bf16[5,256,512,640]{3,2,1,0:T(8,128)(2,1)}) custom-call(%i, %pool), custom_call_target="tpu_custom_call", metadata={op_name="jit(tick)/while/body/closed_call/while/body/closed_call/attn/kv_read/latent_decode/pallas_call"}
  ROOT %out = (s32[]{:T(128)}, bf16[8,512]{1,0:T(8,128)(2,1)S(1)}, bf16[4,1536,8192]{1,2,0:T(8,128)(2,1)}, bf16[4,512,1536]{2,1,0:T(8,128)(2,1)}, bf16[5,256,512,640]{3,2,1,0:T(8,128)(2,1)}) tuple(%i, %x, %stack, %other, %pool)
}

%cond (arg.c: (s32[], bf16[8,512], bf16[4,1536,8192], bf16[4,512,1536], bf16[5,256,512,640])) -> pred[] {
  %arg.c = (s32[]{:T(128)}, bf16[8,512]{1,0:T(8,128)(2,1)S(1)}, bf16[4,1536,8192]{1,2,0:T(8,128)(2,1)}, bf16[4,512,1536]{2,1,0:T(8,128)(2,1)}, bf16[5,256,512,640]{3,2,1,0:T(8,128)(2,1)}) parameter(0)
  ROOT %lt = pred[]{:T(512)} constant(true)
}

ENTRY %main.9 (w: bf16[4,1536,8192], v: bf16[4,512,1536], x0: bf16[8,512], kv: bf16[5,256,512,640]) -> bf16[8,512] {
  %w = bf16[4,1536,8192]{2,1,0:T(8,128)(2,1)} parameter(0), metadata={op_name="params[\\'moe_layers\\'][\\'w_iq\\']"}
  %v = bf16[4,512,1536]{2,1,0:T(8,128)(2,1)} parameter(1), metadata={op_name="params[\\'moe_layers\\'][\\'w_dq\\']"}
  %x0 = bf16[8,512]{1,0:T(8,128)(2,1)} parameter(2), metadata={op_name="x"}
  %kv = bf16[5,256,512,640]{3,2,1,0:T(8,128)(2,1)} parameter(3), metadata={op_name="cache.kv"}
  %zero = s32[]{:T(128)} constant(0)
  %copy.5 = bf16[4,1536,8192]{1,2,0:T(8,128)(2,1)} copy(%w)
  %init = (s32[]{:T(128)}, bf16[8,512]{1,0:T(8,128)(2,1)}, bf16[4,1536,8192]{1,2,0:T(8,128)(2,1)}, bf16[4,512,1536]{2,1,0:T(8,128)(2,1)}, bf16[5,256,512,640]{3,2,1,0:T(8,128)(2,1)}) tuple(%zero, %x0, %copy.5, %v, %kv)
  %while.1 = (s32[]{:T(128)}, bf16[8,512]{1,0:T(8,128)(2,1)S(1)}, bf16[4,1536,8192]{1,2,0:T(8,128)(2,1)}, bf16[4,512,1536]{2,1,0:T(8,128)(2,1)}, bf16[5,256,512,640]{3,2,1,0:T(8,128)(2,1)}) while(%init), condition=%cond, body=%body
  ROOT %res = bf16[8,512]{1,0:T(8,128)(2,1)} get-tuple-element(%while.1), index=1
}
"""


def test_bytes_of_a_shape_skip_the_layout():
    assert hlo_table.shape_bytes("bf16[4,1536,8192]{1,2,0:T(8,128)(2,1)S(1)}") == 4 * 1536 * 8192 * 2
    assert hlo_table.shape_bytes("(f32[8]{0:T(128)S(1)}, bf16[8,1536]{1,0:T(8,128)(2,1)})") == 32 + 8 * 1536 * 2
    assert hlo_table.shape_bytes("s32[]{:T(128)}") == 4


@pytest.mark.parametrize("op,want", [
    # a layer sliced out of the copied stack into fast memory: reads and writes the slice, and says whose copy it slices
    ("constant_dynamic-slice_fusion.7", dict(scope="", mb_read=25.17, mb_written=25.17, reads=["copy.5 of params['moe_layers']['w_iq'] (25.2 MB)"])),
    # a dot whose own (nested) fusion slices the stack: charged the layer, not the stack; the scope is the block's
    ("fusion.9", dict(scope="attn/q_down", mb_read=1.58, mb_written=0.02, reads=["params['moe_layers']['w_dq'] (1.6 MB)"])),
    # the program's entry re-lays out the whole stack
    ("copy.5", dict(scope="", mb_read=100.66, mb_written=100.66, reads=["params['moe_layers']['w_iq'] (100.7 MB)"])),
    # a Pallas call DMAs pages of the pool it is handed whole: no bytes from the text
    ("latent_decode.3", dict(scope="attn/kv_read", mb_read=None, mb_written=None)),
])
def test_a_row_names_the_leaf_and_the_bytes(op, want):
    rows = {r["op"]: r for r in hlo_table.table(HLO, {op: 0.4, "fusion.404": 0.3})}
    assert set(rows) == {op}  # an operation the text does not hold is left out
    for key, value in want.items():
        assert rows[op][key] == value, (key, rows[op])


def test_operations_under_the_threshold_are_left_out_and_the_table_renders():
    rows = hlo_table.table(HLO, {"copy.5": 0.118, "fusion.9": 0.049})
    assert [r["op"] for r in rows] == ["copy.5"]
    text = hlo_table.render(rows)
    assert text.splitlines()[0].startswith("op | ms a step | scope") and "copy.5 | 0.1180 | - | 100.66 | 100.66" in text
