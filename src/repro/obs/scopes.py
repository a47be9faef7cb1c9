"""Model layers by name inside compiled programs.

Every layer of the chip path runs under a ``jax.named_scope`` named by one
of :data:`LAYERS`.  XLA keeps the scope path in each instruction's
``metadata={op_name="..."}`` in the compiled module's text
(``jitted.lower(...).compile().as_text()``), while a profiler trace names a
device operation only by its HLO instruction (``fusion.1321``).
:func:`op_layers` joins the two: it maps every instruction of a compiled
module to its layer and pass, so own time per instruction from a trace
sums to device time per layer.

Scopes are metadata: the compiled instructions, fusions and memory are the
same with or without them.
"""
from __future__ import annotations

import collections
import re

LAYERS = ("embed", "attention", "kv_cache", "moe_router", "moe_dispatch",
          "moe_experts", "moe_combine", "mlp", "ssd", "lru", "unembed_loss",
          "branch", "optimizer")
OTHER = "other"

_INSTR = re.compile(r"^\s*(ROOT\s+)?%?([^\s=]+) = (.*)$")
_COMP = re.compile(r"^\s*(?:ENTRY\s+)?%?([^\s(]+) .*\{\s*$")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_CALLED = re.compile(r"(calls|body|condition|to_apply)=%?([\w.\-]+)")
_REF = re.compile(r"%([\w.\-]+)")
_NUMBER = re.compile(r"(?:\sparameter\(|\), index=)(\d+)")
_WORD = re.compile(r"[A-Za-z_][\w\-]*")
_KEYS = re.compile(r"\[[^\]]*\]")           # an argument's pytree path
_HEAVY = ("dot", "convolution")
# data movement, and the plumbing of tuples and arguments around it
_MOVES = {"bitcast", "copy", "copy-start", "copy-done", "dynamic-slice",
          "dynamic-update-slice", "slice", "reshape", "transpose",
          "concatenate", "pad"}
_PLUMBING = {"parameter", "constant", "get-tuple-element", "tuple"}


def scope_layer(op_name: str) -> tuple[str, str]:
    """``(layer, "fwd" | "bwd")`` of one ``op_name`` path: the innermost
    scope of :data:`LAYERS` on it, else :data:`OTHER`; ``bwd`` where the
    path passes through ``transpose(``, as a gradient's operations do.  An
    argument's op_name is its pytree path (``state['branch']...``), which
    names no scope."""
    layer = OTHER
    for word in _WORD.findall(_KEYS.sub("", op_name)):
        if word in LAYERS:
            layer = word
    return layer, ("bwd" if "transpose(" in op_name else "fwd")


class _Instr:
    __slots__ = ("comp", "op", "own", "refs", "called", "number", "users")

    def __init__(self, comp, rest):
        self.comp = comp
        op = _OPCODE.search(" " + rest)
        self.op = op.group(1) if op else ""
        meta = _OP_NAME.search(rest)
        self.own = scope_layer(meta.group(1)) if meta else None
        self.called = dict(_CALLED.findall(rest))
        skip = set(self.called.values())
        self.refs = [r for r in _REF.findall(rest) if r not in skip]
        num = _NUMBER.search(rest)
        self.number = int(num.group(1)) if num else None
        self.users: list[tuple[str, int]] = []


def op_layers(hlo_text: str) -> dict[str, tuple[str, str]]:
    """``{instruction: (layer, "fwd" | "bwd")}`` for every instruction of a
    compiled module's text, fused ones included.

    An instruction takes the layer of its own ``op_name``.  A fusion takes
    the layer of the first ``dot`` or ``convolution`` it holds, else its
    own, else the layer most of its instructions have: a fusion's own
    metadata is that of one instruction in it, often an elementwise tail,
    while a contraction is its work.

    The compiler drops the metadata of what it makes or moves: a weight's
    cast hoisted out of the layer loop, the copy of a loop's output.  And
    the layer loop's own data movement, the slice of one layer's weights
    or cache and the stacking of its new cache, runs under no layer.  Such
    an instruction takes the layer of the nearest named instruction it is
    made from, following tuple elements through loops, else of the
    nearest that is made from it.  What is left maps to :data:`OTHER`.
    """
    ins: dict[str, _Instr] = {}
    members: dict[str, list[str]] = collections.defaultdict(list)
    root: dict[str, str] = {}
    params: dict[str, dict[int, str]] = collections.defaultdict(dict)
    current = None
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m and current is not None:
            is_root, name, rest = m.groups()
            i = ins[name] = _Instr(current, rest)
            members[current].append(name)
            if is_root:
                root[current] = name
            if i.op == "parameter" and i.number is not None:
                params[current][i.number] = name
            continue
        c = _COMP.match(line)
        if c:
            current = c.group(1)
    callers: dict[str, list[str]] = collections.defaultdict(list)
    for name, i in ins.items():
        i.refs = [r for r in i.refs if r in ins]
        for pos, r in enumerate(i.refs):
            ins[r].users.append((name, pos))
        for comp in i.called.values():
            callers[comp].append(name)

    memo: dict[str, tuple] = {}

    def resolve(name: str):
        """(layer and pass, None where data flow decides; whether it is or
        holds a contraction; whether it only moves data)"""
        if name not in memo:
            i = ins[name]
            got, heavy = i.own, i.op in _HEAVY
            moves = i.op in _MOVES or i.op in _PLUMBING
            if i.op == "fusion":
                inner = [resolve(n) for n in members[i.called.get("calls")]]
                named = [r[0] for r in inner if r[0] and r[0][0] != OTHER]
                contractions = [r[0] for r in inner
                                if r[1] and r[0] and r[0][0] != OTHER]
                heavy = any(r[1] for r in inner)
                moves = all(r[2] for r in inner)
                if contractions:
                    got = contractions[0]
                elif named and (got is None or got[0] == OTHER):
                    got = collections.Counter(named).most_common(1)[0][0]
            if got and got[0] == OTHER and moves and i.op not in _PLUMBING:
                got = None
            memo[name] = (got, heavy, moves)
        return memo[name]

    def producers(name, k):
        i = ins[name]
        if i.op == "get-tuple-element":
            return [(i.refs[0], i.number)] if i.refs else []
        if i.op == "tuple":
            return ([(i.refs[k], None)] if k is not None and k < len(i.refs)
                    else [(r, None) for r in i.refs])
        if i.op == "while":
            return [(root[i.called["body"]], k)] if "body" in i.called else []
        if i.op == "parameter":
            return [(ins[c].refs[0 if ins[c].op == "while" else i.number], k)
                    for c in callers[i.comp]
                    if i.number is not None and len(ins[c].refs) > (
                        0 if ins[c].op == "while" else i.number)]
        return [(r, None) for r in i.refs]

    def consumers(name, k):
        out = []
        if root.get(ins[name].comp) == name:
            out += [(c, k) for c in callers[ins[name].comp]
                    if ins[c].op == "while"]
        for u, pos in ins[name].users:
            j = ins[u]
            if j.op == "tuple":
                out.append((u, pos))
            elif j.op == "get-tuple-element":
                if k is None or j.number == k:
                    out.append((u, None))
            elif j.op == "while":
                out += [(params[j.called[c]][0], k) for c in ("body",
                        "condition") if 0 in params.get(j.called.get(c), {})]
            else:
                out.append((u, None))
        return out

    def nearest(name, step, limit: int = 256):
        seen, frontier = {(name, None)}, [(name, None)]
        while frontier and len(seen) < limit:
            nxt = []
            for node in frontier:
                for n in step(*node):
                    if n in seen:
                        continue
                    seen.add(n)
                    got = resolve(n[0])[0]
                    if got is not None and got[0] != OTHER:
                        return got
                    nxt.append(n)
            frontier = nxt
        return None

    out = {}
    for name in ins:
        got = resolve(name)[0]
        if got is None:
            got = (nearest(name, producers) or nearest(name, consumers)
                   or (OTHER, "fwd"))
        out[name] = got
    return out
