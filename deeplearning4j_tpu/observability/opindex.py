"""Which layer and phase a device op belongs to.

A device trace names an op by its HLO instruction (``%fusion.136``) and
nothing ties that to the code that produced it. The compiled program
does: every instruction carries ``op_name="jit(multi)/while/body/
closed_call/transpose(jvp(<scope>))/mul"``, where JAX writes the phase
(``jvp(`` forward, ``transpose(jvp(`` backward) and the primitive, and
the nets write the layer: ``scope(layer.name)`` round each layer's
``apply`` (nn/multilayer.py, nn/graph.py), ``update`` and ``loss`` in
``precision.build_step_fn``. Scopes exist at trace time only.

``register`` notes, once per (step program, batch shapes), what was
dispatched; ``lookup`` compiles that program again on demand (a load,
with the persistent cache on) and parses its text; ``place`` reads one
entry. See OBSERVABILITY.md, "Which layer is a device op?".
"""

from __future__ import annotations

import re
import time
import weakref
from collections import Counter
from typing import Optional

import jax

from deeplearning4j_tpu.observability.trace import get_tracer

__all__ = ["scope", "scope_name", "register", "lookup", "parse", "place",
           "contains", "PHASES"]

PHASES = ("forward", "backward", "update", "loss", "input", "unplaced")

_scopes = {"update", "loss"}    # every name a net has entered
_seen: dict = {}                # id(jitted) -> key of its last registration
_programs: dict = {}            # module name -> newest _Program


def scope_name(name: str) -> str:
    """``name`` with every character outside ``[A-Za-z0-9_]`` replaced by
    ``_``: XLA derives instruction names from scopes, and readers match
    those with ``[\\w.]``."""
    return re.sub(r"[^A-Za-z0-9_]", "_", str(name))


def scope(name: str):
    """``jax.named_scope`` of the cleaned ``name``, remembered so that
    ``place`` can tell a layer from JAX's own path elements."""
    clean = scope_name(name)
    _scopes.add(clean)
    return jax.named_scope(clean)


class _Program:
    def __init__(self, jitted, args):
        self.jitted = weakref.ref(jitted)
        self.specs = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=getattr(a, "sharding", None)),
            args)
        self.index = None


def register(jitted, args, data) -> None:
    """Note that ``jitted(*args)`` is being dispatched. ``data`` is the
    part of ``args`` whose shapes change between dispatches (the batch).
    A dispatch whose key was seen pays this comparison and nothing else;
    nothing is lowered, compiled or parsed before ``lookup``."""
    leaves, treedef = jax.tree_util.tree_flatten(data)
    key = (treedef, tuple(a.shape for a in leaves))
    if _seen.get(id(jitted)) == key:
        return
    if id(jitted) not in _seen:
        weakref.finalize(jitted, _seen.pop, id(jitted), None)
    _seen[id(jitted)] = key
    _programs["jit_" + getattr(jitted, "__name__", "")] = _Program(
        jitted, args)


def lookup(module_name: str) -> Optional[dict]:
    """Instruction name (as a trace prints it, without ``%``) to
    ``{"opcode", "op_name", "inner"}`` for the newest registered program
    the profiler calls ``module_name`` (``jit_multi``, ``jit_step_fn``);
    None when there is none or it is gone. Memoised per registration."""
    program = _programs.get(module_name)
    jitted = program and program.jitted()
    if jitted is None:
        return None
    if program.index is None:
        with get_tracer().program_span("opindex_lookup",
                                       module=module_name) as sp:
            text = jitted.lower(*program.specs).compile().as_text()
            t0 = time.perf_counter()
            program.index = parse(text)
            sp.set(parse_s=round(time.perf_counter() - t0, 6))
    return program.index


_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.-]+) \(.*\{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.-]+) = (.*)$")
_OPCODE = re.compile(r"[\]})] ([a-z][\w-]*)\(([^)]*)")
_OPERAND = re.compile(r"%([\w.-]+)")
_OP_NAME = re.compile(r'op_name="([^";]*)')
_CALLED = re.compile(r"\b(calls|to_apply)=%?([\w.-]+)")
_NO_HOME = ("parameter", "constant", "tuple", "get-tuple-element", "bitcast")


def parse(hlo_text: str) -> dict:
    """One pass over a compiled module's text: every instruction that
    runs as an op of its own (those inside a fusion or a reduction's
    region are reached through ``inner``). ``inner`` of a fusion is the
    ``(opcode, op_name)`` of every instruction of the computation it
    ``calls=``, nested fusions flattened. An instruction XLA added
    without metadata (``copy-start``, ``copy-done``) takes ``op_name``
    and ``inner`` of the nearest instruction that has them, along its
    first operands and then its first users, named under ``"via"``."""
    computations: dict = {}
    inlined = set()     # fused computations and reduction regions
    body = None
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c:
                body = computations.setdefault(c.group(1), {})
            continue
        op = _OPCODE.search(m.group(2))
        if op is None or body is None:
            continue
        found = _OP_NAME.search(m.group(2))
        called = _CALLED.search(m.group(2))
        calls = None
        if called and op.group(1) != "call":
            inlined.add(called.group(2))
            if called.group(1) == "calls":
                calls = called.group(2)
        body[m.group(1)] = {"opcode": op.group(1),
                            "op_name": found.group(1) if found else "",
                            "calls": calls, "users": [],
                            "operands": _OPERAND.findall(op.group(2))}

    def flatten(computation, depth=0):
        out = []
        for e in computations.get(computation, {}).values():
            if e["calls"] and depth < 8:
                out += flatten(e["calls"], depth + 1)
            elif e["opcode"] not in _NO_HOME:
                out.append((e["opcode"], e["op_name"]))
        return out

    index = {name: entry for c, body in computations.items()
             if c not in inlined for name, entry in body.items()}
    for name, entry in index.items():
        entry["inner"] = flatten(entry["calls"]) if entry["calls"] else []
        for operand in entry["operands"]:
            if operand in index:
                index[operand]["users"].append(name)

    def named(entry):
        return entry["op_name"] or any(n for _, n in entry["inner"])

    def nearest(name, side):
        for _ in range(8):
            name = next((n for n in index[name][side] if n in index), None)
            if name is None or named(index[name]):
                return name
        return None

    for name, entry in index.items():
        via = None if named(entry) else (
            nearest(name, "operands") or nearest(name, "users"))
        if via:
            entry["via"] = via
    for entry in index.values():
        if "via" in entry:
            source = index[entry["via"]]
            entry.update(op_name=source["op_name"], inner=source["inner"])
        del entry["calls"], entry["operands"], entry["users"]
    return index


_WEIGHT = {"convolution": 3, "dot": 2, "custom-call": 2, "reduce": 1,
           "reduce-window": 1, "select-and-scatter": 1}
_ELEMENT = re.compile(r"^(?:(?:jvp|transpose|vmap)\()*([^()]*)\)*$")


def _place_path(op_name: str, scopes) -> tuple:
    parts = op_name.split("/")
    layer = ""
    for part in parts[:-1]:
        m = _ELEMENT.match(part)
        if m and m.group(1) in scopes:
            layer = m.group(1)
    primitive = parts[-1]
    if "transpose(" in op_name or (
            "jvp(" in op_name and ("checkpoint" in op_name
                                   or "rematted_computation" in op_name)):
        return "backward", layer, primitive
    if layer in ("update", "loss"):
        return layer, layer, primitive
    if "jvp(" in op_name:
        return "forward", layer, primitive
    return "input", layer, primitive


def place(entry: Optional[dict], scopes=None) -> tuple:
    """``(phase, layer, primitive)`` of one entry of ``lookup``.

    Phase is ``forward`` (``jvp(`` in the path), ``backward``
    (``transpose(``, or a forward recomputed under remat), ``update`` or
    ``loss`` (those scopes), ``input`` (named by JAX but outside the
    step: stacking, slicing, converts, the rng split) or ``unplaced``
    (no entry, or no name anywhere). Layer is the innermost scope a net
    entered; ``scopes`` replaces that set for an index read from a file.
    A fusion is placed by its heaviest inner instructions (convolution >
    dot, custom-call > reduce, reduce-window, select-and-scatter > the
    rest), the most frequent placement among them: its own name comes
    from its root, the epilogue. A collective keeps the ``op_name`` of
    the op it was split from, so an all-reduce under ``transpose(jvp(``
    is a gradient and one under ``jvp(<batch norm>)`` a statistic."""
    if entry is None:
        return "unplaced", "", ""
    scopes = _scopes if scopes is None else scopes
    named = [(op, n) for op, n in entry["inner"] if n]
    if not named:
        if not entry["op_name"]:
            return "unplaced", "", entry["opcode"]
        return _place_path(entry["op_name"], scopes)
    top = max(_WEIGHT.get(op, 0) for op, _ in named)
    votes = Counter(_place_path(n, scopes) for op, n in named
                    if _WEIGHT.get(op, 0) == top)
    # a slice or convert fused in decides nothing where a layer's op is
    placed = Counter({p: n for p, n in votes.items() if p[0] != "input"})
    return (placed or votes).most_common(1)[0][0]


def contains(entry: Optional[dict], opcode: str) -> bool:
    """Whether the instruction is, or fuses, an ``opcode``."""
    return entry is not None and (
        entry["opcode"] == opcode
        or any(op == opcode for op, _ in entry["inner"]))
