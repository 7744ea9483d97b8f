"""tools/step_hash.normalize: what it may erase and what it must keep.

"Equal hashes" is the proof a refactor offers that a cell's compiled step is
the parent's; a normalizer that erased too much would make it a false one.
"""

import base64
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

import step_hash  # noqa: E402


def kernel(op: str, file: str) -> str:
    """A serialized Mosaic kernel's place in a custom call: base64 MLIR."""
    text = (f'module {{ %0 = "t.const"() {{value = 1 : i32}} : () -> i32 '
            f'loc("{file}":3:7) %1 = "t.{op}"(%0, %0) : (i32, i32) -> i32 '
            f'loc("/checkout/{file}":9:1) }}')
    return base64.b64encode(text.encode()).decode()


def hlo(n=7, clone=34, line=120, op="add", lhs="p", shape="f32[8,3]",
        value="1.5", kernel_op="add", file="halo.py", sep=":"):
    operands = [f"param_0.{n + 1}", f"param_1.{n + 2}"]
    if lhs == "q":
        operands.reverse()
    return f"""HloModule jit_potential, entry_computation_layout={{(f32[8,3]{{1,0}})->f32[8,3]{{1,0}}}}

FileNames
1 "/root/repo/distmlip_tpu/parallel/{file}"
2 "/root/repo/distmlip_tpu/models/mace.py"

FunctionNames
1 "scan_edges"

FileLocations
1 {{file_name_id=1 function_name_id=1 line={line} end_line={line} column=4 end_column=9}}

StackFrames
1 {{file_location_id=1 parent_frame_id=1}}

fused_computation.{n}.clone.{clone} (param_0.{n + 1}: {shape}, param_1.{n + 2}: {shape}) -> {shape} {{
  param_0.{n + 1} = {shape}{{1,0}} parameter(0)
  param_1.{n + 2} = {shape}{{1,0}} parameter(1)
  constant.{n + 3}.clone.{clone} = f32[] constant({value})
  ROOT {op}.{n + 4} = {shape}{{1,0}} {op}({", ".join(operands)}), metadata={{op_name="jit(potential)/edge_aggregate/add" source_file="/root/repo/distmlip_tpu/parallel/{file}" source_line={line} stack_frame_id=1}}
}}

ENTRY main.{n + 9} (p: {shape}, q: {shape}) -> {shape} {{
  p = {shape}{{1,0}} parameter(0), metadata={{op_name="positions"}}
  q = {shape}{{1,0}} parameter(1)
  custom-call.{n + 5} = {shape}{{1,0}} custom-call(p), custom_call_target="tpu_custom_call", backend_config={{"custom_call_config": {{"body"{sep}"{kernel(kernel_op, file)}", "serialization_format":1}}}}
  ROOT fusion.{n + 6} = {shape}{{1,0}} fusion(custom-call.{n + 5}, q), kind=kLoop, calls=fused_computation.{n}.clone.{clone}, metadata={{op_name="jit(potential)/edge_gather" source_line={line + 1}}}
}}
"""


@pytest.mark.parametrize("change, same", [
    (dict(n=1863), True),                 # every name renumbered
    (dict(clone=5), True),                # ... the numbers after .clone. too
    (dict(line=517), True),               # metadata and the file tables
    (dict(file="chunk.py"), True),        # source path, a kernel's locations
    (dict(n=40, clone=2, line=9, file="x.py"), True),
    (dict(file="chunk.py", sep=": "), True),  # the body after a space
    (dict(op="multiply"), False),         # another instruction
    (dict(lhs="q"), False),               # the same instructions, wired anew
    (dict(shape="f32[16,3]"), False),
    (dict(value="2.5"), False),           # a number that is no name
    (dict(kernel_op="mul"), False),       # another kernel body
    (dict(kernel_op="mul", sep=": "), False),
])
def test_normalize_erases_names_and_places_only(change, same):
    base, other = hlo(), hlo(**change)
    assert base != other
    assert (step_hash.normalize(base) == step_hash.normalize(other)) is same


def test_normalized_text_keeps_instructions_and_drops_the_rest():
    text = step_hash.normalize(hlo())
    assert "metadata=" not in text and "halo.py" not in text
    assert "FileNames" not in text and "file_name_id" not in text
    assert kernel("add", "halo.py") not in text
    assert "fused_computation.clone#1" in text and "constant.clone#1" in text
    assert "calls=fused_computation.clone#1" in text
    # distinct instructions stay distinct after renumbering
    assert "param_0#1" in text and "param_1#1" in text
    assert "constant(1.5)" in text and 'custom_call_target="tpu_custom_call"' in text
    assert text.count("\n") + 1 == 15
