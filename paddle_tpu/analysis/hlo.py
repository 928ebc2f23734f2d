"""Minimal text parsers for the two program dumps the linter inspects.

No HLO python bindings exist for the AOT TPU pipeline's output, but the
two facts the detectors need — instruction-level def/use in the ENTRY
computation of optimized HLO, and SSA def/use in lowered StableHLO — are
regular enough to parse from `Compiled.as_text()` / `Lowered.as_text()`.
Kept deliberately narrow: shapes, layout *permutations* (tiling and
memory-space suffixes like ``T(8,128)S(1)`` are ignored — a
same-permutation copy is a memory-space move, not a relayout), operand
name lists, and the module-header ``input_output_alias`` /
``entry_computation_layout`` blocks.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

__all__ = [
    "HloInstr",
    "Shape",
    "entry_instructions",
    "parse_entry_layout",
    "parse_input_output_alias",
    "parse_shape",
    "shape_bytes",
    "stablehlo_broadcast_operands",
]

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8, "f16": 2, "bf16": 2, "f32": 4, "f64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16,
    # StableHLO spellings
    "i1": 1, "i8": 1, "i16": 2, "i32": 4, "i64": 8,
}


@dataclass
class Shape:
    dtype: str
    dims: Tuple[int, ...]
    perm: str = ""  # layout permutation, "" when unspecified/scalar

    @property
    def bytes(self) -> int:
        n = _DTYPE_BYTES.get(self.dtype, 4)
        for d in self.dims:
            n *= d
        return n


@dataclass
class HloInstr:
    name: str
    opcode: str
    shapes: List[Shape]               # result shapes (tuple flattened)
    # (shape, name) per operand defined in the same computation; the
    # compiler prints operands as bare %refs, so the shape is the
    # producer's (None where the producer is a tuple)
    operands: List[Tuple[Optional[Shape], str]]
    operand_names: List[str]          # every %ref on the line, in order
    is_root: bool = False
    line: str = ""


# f32[2,56,56,64]{3,2,1,0:T(8,128)S(1)}  /  f32[]{:T(128)}  /  s32[4,32]
_SHAPE_RE = re.compile(
    r"([a-z][a-z0-9]*)\[([\d,]*)\](?:\{([^}]*)\})?")
_DEF_RE = re.compile(r"^\s*(ROOT\s+)?%([\w.\-]+)\s*=\s*")


def parse_shape(text: str) -> Optional[Shape]:
    m = _SHAPE_RE.search(text)
    if not m:
        return None
    dims = tuple(int(d) for d in m.group(2).split(",") if d)
    layout = m.group(3) or ""
    perm = layout.split(":", 1)[0]
    return Shape(m.group(1), dims, perm)


def shape_bytes(text: str) -> int:
    s = parse_shape(text)
    return s.bytes if s else 0


def _result_shapes(text: str) -> List[Shape]:
    return [Shape(m.group(1),
                  tuple(int(d) for d in m.group(2).split(",") if d),
                  (m.group(3) or "").split(":", 1)[0])
            for m in _SHAPE_RE.finditer(text)]


_REF_RE = re.compile(r"%([\w.\-]+)")


def _scan_result_shape(text: str):
    """Parse the result-shape prefix of an instruction body (single shape
    or tuple; layouts nest () and {} — e.g. T(8,128) — so this scans by
    depth).  Returns (shape_text, rest) or None."""
    text = text.lstrip()
    if text.startswith("("):
        depth, i = 1, 1
        while depth and i < len(text):
            depth += {"(": 1, ")": -1}.get(text[i], 0)
            i += 1
        return text[:i], text[i:]
    m = _SHAPE_RE.match(text)
    if not m:
        return None
    i = m.end()
    if i < len(text) and text[i] == "{":
        depth = 1
        i += 1
        while depth and i < len(text):
            depth += {"{": 1, "}": -1}.get(text[i], 0)
            i += 1
    return text[:i], text[i:]


_OPCODE_RE = re.compile(r"\s*([\w\-]+)\(")


def entry_instructions(hlo_text: str) -> List[HloInstr]:
    """Instructions of the ENTRY computation only — fusion-internal ops
    never touch HBM on their own, so relayout/copy accounting over them
    would double-count."""
    out: List[HloInstr] = []
    in_entry = False
    for line in hlo_text.splitlines():
        if line.startswith("ENTRY "):
            in_entry = True
            continue
        if in_entry and line.startswith("}"):
            break
        if not in_entry:
            continue
        m = _DEF_RE.match(line)
        if not m:
            continue
        scanned = _scan_result_shape(line[m.end():])
        if not scanned:
            continue
        shape_txt, rest = scanned
        om = _OPCODE_RE.match(rest)
        if not om:
            continue
        rest = rest[om.end():]
        # operands end at the opcode's matching close paren; trailing
        # attrs (metadata/backend_config) must not contribute refs
        depth, j = 1, 0
        while depth and j < len(rest):
            depth += {"(": 1, ")": -1}.get(rest[j], 0)
            j += 1
        rest = rest[:max(j - 1, 0)]
        out.append(HloInstr(
            name=m.group(2),
            opcode=om.group(1),
            shapes=_result_shapes(shape_txt),
            operands=[],
            operand_names=_REF_RE.findall(rest),
            is_root=bool(m.group(1)),
            line=line.strip(),
        ))
    by_name = {i.name: i for i in out}
    for instr in out:
        instr.operands = [
            (by_name[n].shapes[0] if len(by_name[n].shapes) == 1 else None, n)
            for n in instr.operand_names if n in by_name]
    return out


def parse_entry_layout(hlo_text: str):
    """(param_shapes, output_shapes) from the module header's
    entry_computation_layout={(p0, p1, ...)->(o0, ...)}."""
    m = re.search(r"entry_computation_layout=\{", hlo_text)
    if not m:
        return [], []
    # shape layouts contain nested {}: scan to the matching close brace
    depth, i = 1, m.end()
    while depth and i < len(hlo_text):
        depth += {"{": 1, "}": -1}.get(hlo_text[i], 0)
        i += 1
    body = hlo_text[m.end():i - 1]
    if "->" not in body:
        return [], []
    params_txt, out_txt = body.split("->", 1)
    params = [parse_shape(p) for p in _split_shapes(params_txt)]
    outs = [parse_shape(o) for o in _split_shapes(out_txt)]
    return [p for p in params if p], [o for o in outs if o]


def _split_shapes(text: str) -> List[str]:
    """Split '(f32[2]{1,0:T(8,128)}, f32[]{:T(128)})' on top-level commas
    (commas also appear inside [] and {})."""
    text = text.strip()
    if text.startswith("("):
        text = text[1:]
    if text.endswith(")"):
        text = text[:-1]
    parts, depth, cur = [], 0, []
    for ch in text:
        if ch in "[{(":
            depth += 1
        elif ch in "]})":
            depth -= 1
        if ch == "," and depth == 0:
            parts.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    if cur:
        parts.append("".join(cur))
    return [p for p in (s.strip() for s in parts) if p]


_ALIAS_RE = re.compile(
    r"\{([\d,\s]*)\}:\s*\((\d+),\s*\{[\d,\s]*\}(?:,\s*([a-z\-]+))?\)")


def parse_input_output_alias(hlo_text: str) -> Dict[int, int]:
    """{flat output index: parameter number} from the module header's
    input_output_alias block (empty dict when nothing is aliased).  Only
    flat (non-nested) output tuples are produced by our step functions."""
    m = re.search(r"input_output_alias=\{", hlo_text)
    if not m:
        return {}
    depth, i = 1, m.end()
    while depth and i < len(hlo_text):
        depth += {"{": 1, "}": -1}.get(hlo_text[i], 0)
        i += 1
    out: Dict[int, int] = {}
    for am in _ALIAS_RE.finditer(hlo_text[m.end():i - 1]):
        idx_txt = am.group(1).strip()
        out_idx = int(idx_txt.split(",")[0]) if idx_txt else 0
        out[out_idx] = int(am.group(2))
    return out


# ---------------------------------------------------------------------------
# StableHLO (lowered, pre-XLA-pipeline) — SSA def/use for the broadcast
# detector.

_SH_TENSOR_RE = re.compile(r"tensor<([^>]*)>")
_SH_BCAST_RE = re.compile(
    r"%([\w#]+)\s*=\s*(?:\"stablehlo\.broadcast_in_dim\"|"
    r"stablehlo\.broadcast_in_dim)\s*[\(]?%([\w#]+)")
_SH_CC_RE = re.compile(
    r"(?:\"stablehlo\.custom_call\"|stablehlo\.custom_call)\s*"
    r"(?:@([\w.]+)\s*)?\(([^)]*)\)")


def _tensor_elems_bytes(type_txt: str) -> int:
    parts = type_txt.split("x")
    dtype = parts[-1]
    n = _DTYPE_BYTES.get(dtype, 4)
    for d in parts[:-1]:
        if d.isdigit():
            n *= int(d)
    return n


def stablehlo_broadcast_operands(sh_text: str):
    """Yield (cc_target, operand_ssa_name, materialized_bytes,
    source_bytes) for every custom-call operand whose defining op is a
    materializing stablehlo.broadcast_in_dim (result strictly larger than
    its source)."""
    bcasts = {}
    for line in sh_text.splitlines():
        bm = _SH_BCAST_RE.search(line)
        if bm:
            types = _SH_TENSOR_RE.findall(line)
            if len(types) >= 2:
                src_b = _tensor_elems_bytes(types[-2])
                dst_b = _tensor_elems_bytes(types[-1])
                bcasts[bm.group(1)] = (dst_b, src_b, line.strip())
            continue
    results = []
    for line in sh_text.splitlines():
        cm = _SH_CC_RE.search(line)
        if not cm:
            continue
        target = cm.group(1) or ""
        for ref in _REF_RE.findall(cm.group(2)):
            if ref in bcasts:
                dst_b, src_b, _ = bcasts[ref]
                if dst_b > src_b:
                    results.append((target, ref, dst_b, src_b))
    return results
