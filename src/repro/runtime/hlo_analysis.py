"""Compiled-HLO analysis: collective bytes + roofline terms.

``cost_analysis()`` gives FLOPs and HBM bytes but NOT collective traffic;
we parse the optimized HLO text and sum the operand sizes of every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute
instruction (assignment §Roofline).

Two XLA-text realities this parser handles (verified on jax 0.8 CPU):
* post-optimization HLO prints operands WITHOUT types
  (``all-gather(%copy)``) — the OUTPUT type is always present, so operand
  bytes are recovered from it: /group_size for all-gather, x group_size
  for reduce-scatter, identity otherwise (group size parsed from
  ``replica_groups=[G,S]<=`` or explicit group lists);
* loop bodies are separate computations and appear ONCE in the text while
  executing trip_count times — collectives are therefore attributed to
  top-level vs in-loop regions, and the caller scales in-loop bytes by the
  known scan trip count (layer stacks / grad-accum are compile-time
  constants of our models).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Iterable

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "token": 0, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_INSTR_RE = re.compile(
    r"=\s+(\([^)]*\)|[a-z0-9_]+\[[0-9,]*\](?:\{[^}]*\})?)\s+"
    r"(" + "|".join(_COLLECTIVES) + r")(-start|-done)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
# replica_groups=[2,4]<=[8]  -> 2 groups of size 4
_RG_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=")
# replica_groups={{0,1,2,3},{4,5,6,7}}
_RG_LIST_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")
# computation headers: `%name (args) -> type {` or `ENTRY %name ...`
_COMP_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*(?:\([^)]*\))?\s*->.*\{")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str) -> int:
    m = _RG_IOTA_RE.search(line)
    if m:
        return max(int(m.group(2)), 1)
    m = _RG_LIST_RE.search(line)
    if m:
        return max(len(m.group(1).split(",")), 1)
    return 1


def _operand_bytes(line: str, op: str, out_type: str) -> int:
    """Operand bytes; falls back to output-shape arithmetic when the
    operand list carries no types (post-optimization HLO)."""
    i = line.index("(", line.index(op))
    depth = 0
    j_end = len(line)
    for j in range(i, len(line)):
        if line[j] == "(":
            depth += 1
        elif line[j] == ")":
            depth -= 1
            if depth == 0:
                j_end = j + 1
                break
    inline = _shape_bytes(line[i:j_end])
    if inline:
        return inline
    out = _shape_bytes(out_type)
    g = _group_size(line)
    if op == "all-gather":
        return out // g
    if op == "reduce-scatter":
        return out * g
    return out


@dataclasses.dataclass
class CollectiveStats:
    bytes_by_op: Dict[str, int]
    count_by_op: Dict[str, int]
    in_loop_bytes: int = 0
    top_level_bytes: int = 0

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_op.values())

    def scaled_total(self, loop_trip_count: int) -> int:
        """Total with in-loop collectives executed ``loop_trip_count`` times."""
        return self.top_level_bytes + self.in_loop_bytes * loop_trip_count


def collective_bytes(hlo_lines: Iterable[str]) -> CollectiveStats:
    bytes_by_op: Dict[str, int] = {}
    count_by_op: Dict[str, int] = {}
    in_loop = 0
    top = 0
    cur_comp_is_loop = False
    for line in hlo_lines:
        stripped = line.strip()
        cm = _COMP_RE.match(stripped)
        if cm and stripped.endswith("{"):
            name = cm.group(2)
            is_entry = bool(cm.group(1)) or name.startswith("main")
            cur_comp_is_loop = (not is_entry) and (
                "while" in name or "body" in name or "cond" in name
                or "region" in name)
            continue
        m = _INSTR_RE.search(line)
        if not m:
            continue
        out_type, op, suffix = m.group(1), m.group(2), m.group(3) or ""
        if suffix == "-done":
            continue
        b = _operand_bytes(line, op, out_type)
        bytes_by_op[op] = bytes_by_op.get(op, 0) + b
        count_by_op[op] = count_by_op.get(op, 0) + 1
        if cur_comp_is_loop:
            in_loop += b
        else:
            top += b
    return CollectiveStats(bytes_by_op=bytes_by_op, count_by_op=count_by_op,
                           in_loop_bytes=in_loop, top_level_bytes=top)


def collective_bytes_from_text(hlo_text: str) -> CollectiveStats:
    return collective_bytes(hlo_text.splitlines())


# input_output_alias={ {0}: (0, {}, may-alias), {1}: (2, {1}, must-alias) }
_ALIAS_HDR_RE = re.compile(r"input_output_alias=\{(.*?)\}\s*(?:,|$)")
_ALIAS_ENTRY_RE = re.compile(r"\{[0-9,\s]*\}:\s*\((\d+),")


def input_output_aliases(hlo_text: str) -> list:
    """Parse the entry computation's ``input_output_alias`` header from
    compiled HLO text: a list of parameter indices, one per aliased output
    position (the XLA encoding of jit buffer donation). Empty when nothing
    was donated."""
    out = []
    for line in hlo_text.splitlines():
        if "input_output_alias=" not in line:
            continue
        seg = line.split("input_output_alias=", 1)[1]
        # The alias map is a brace-balanced {...} blob on the module header.
        depth = 0
        for j, ch in enumerate(seg):
            if ch == "{":
                depth += 1
            elif ch == "}":
                depth -= 1
                if depth == 0:
                    seg = seg[:j + 1]
                    break
        out.extend(int(m.group(1))
                   for m in _ALIAS_ENTRY_RE.finditer(seg))
    return out


def donated_params(hlo_text: str) -> set:
    """Parameter indices whose buffers the compiled executable reuses for
    outputs (donation landed, memory stays flat in those operands)."""
    return set(input_output_aliases(hlo_text))


def compiled_hlo_text(fn, mesh, in_specs, out_spec, avals) -> str:
    """Optimized HLO text of ``fn`` compiled under ``mesh``.

    Shardings are expressed as explicit ``NamedSharding``s on the jit
    boundary — the stable ``jax.sharding`` surface — rather than the
    removed ``jax.set_mesh`` context-manager API.

    Args:
      fn: function to lower.
      mesh: a ``jax.sharding.Mesh``.
      in_specs: one ``PartitionSpec`` per positional argument.
      out_spec: ``PartitionSpec`` for the (single) output.
      avals: one ``jax.ShapeDtypeStruct`` per positional argument.
    """
    import jax
    from jax.sharding import NamedSharding

    jitted = jax.jit(
        fn,
        in_shardings=tuple(NamedSharding(mesh, s) for s in in_specs),
        out_shardings=NamedSharding(mesh, out_spec))
    return jitted.lower(*avals).compile().as_text()


_WHILE_RE = re.compile(r"=\s.*?\swhile\(")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')


def while_loops(hlo_text: str) -> list:
    """The ``op_name`` metadata of every ``while`` instruction in compiled
    HLO text ("" where it has none), one entry per loop the executable
    holds, so a caller can count the loops of a named scope."""
    out = []
    for line in hlo_text.splitlines():
        if _WHILE_RE.search(line):
            m = _OP_NAME_RE.search(line)
            out.append(m.group(1) if m else "")
    return out
