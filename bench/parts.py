"""Device time per part of a paper stage, with a place for the ops that the
compiled module gives no scope.

The round program puts a second level of named scopes inside its stages,
named in ``repro.telemetry.spans.PARTS`` and read from there at each call,
so that a part the program adds is reduced as it is: today ``cohort``,
``sgd`` and ``agg`` under ``train``, ``pdd`` under ``schedule``, and
``round_loop`` around the scan over rounds, under no stage.  ``part_of``
names an op's part from its scope path: ``<stage>/<part>``,
``<stage>/rest`` for an op under a stage but no part of it,
``other/round_loop``, and ``other/rest`` for the ops that nothing places.

XLA gives some ops no ``op_name`` metadata at all: fusions it formed,
copies and bitcasts it put in for layouts, clones of broadcasts, the loops
it makes of a large gather.  ``fallback_scopes`` places them from the
compiled module's text, by the first of these that has a scope:

1. the instructions of the computation the op calls (a fusion's fused
   computation), by the part most of them carry;
2. the nearest producer of its operands, where a parameter of a loop body
   or called computation leads on to the op that calls it;
3. the nearest op that uses its result, where the root of a called
   computation leads on to the op that calls it.

A scope under a stage is looked for along all three before a scope under
none (``round_loop``, or the call that wraps the round's body) is taken.
Parameters and constants never lend their scope: a parameter's
``op_name`` is the argument's name, and XLA shares one constant among
ops of many scopes.

``reduce_parts`` reduces a trace by part with that fallback.
``tracing.reduce_events``, and the per-stage metrics it feeds, read no
fallback: an op that only the fallback places counts under ``other``
there and under its part here, so ``cohort_ms + sgd_ms + agg_ms`` may
exceed ``train_ms``.
"""
from __future__ import annotations

import re
from collections import Counter, defaultdict, deque
from typing import Dict, List, Optional, Tuple

from repro.telemetry import spans

from bench import tracing

_HEADER = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{\s*$")
_INSTR = re.compile(r"^\s*(ROOT )?%([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPCODE = re.compile(r"(?:^|\s)([a-z][\w\-]*)\(")
_CALLS = re.compile(r"\b(?:calls|body|condition|to_apply)=%([\w.\-]+)")
_FUSED = re.compile(r"\bcalls=%([\w.\-]+)")
_REF = re.compile(r"%([\w.\-]+)")
_NO_SCOPE = ("parameter", "constant")
_CONTROL = ("while", "call", "conditional")


def part_of(scope: str) -> str:
    """``<stage>/<part>`` of a scope path: the innermost stage, as
    ``tracing.stage_of`` finds it, and the innermost part inside it."""
    comps = scope.replace("(", "/").replace(")", "/").split("/")
    at = max((i for i, c in enumerate(comps) if c in tracing.STAGES),
             default=-1)
    stage = comps[at] if at >= 0 else "other"
    inner = [c for c in comps[at + 1:] if c in spans.PARTS]
    return f"{stage}/{inner[-1] if inner else 'rest'}"


def _staged(scope: str) -> bool:
    return not part_of(scope).startswith("other/")


class _Module:
    """The instructions of a compiled module's text: each one's own scope,
    opcode, computation and operands, and who calls each computation."""

    def __init__(self, hlo_text: str):
        self.scope: Dict[str, Optional[str]] = {}
        self.opcode: Dict[str, str] = {}
        self.comp: Dict[str, str] = {}
        self.fused: Dict[str, str] = {}
        self.body: Dict[str, List[str]] = defaultdict(list)
        self.root: Dict[str, str] = {}
        self.callers: Dict[str, List[str]] = defaultdict(list)
        refs: Dict[str, List[str]] = {}
        comp = ""
        for line in hlo_text.splitlines():
            m = _INSTR.match(line)
            if m is None:
                h = _HEADER.match(line)
                if h is not None:
                    comp = h.group(1)
                continue
            is_root, name, rhs = m.groups()
            head = rhs.split(", metadata=", 1)[0]
            meta = _OP_NAME.search(rhs)
            self.scope[name] = meta.group(1) if meta else None
            op = _OPCODE.search(re.sub(r"\{[^{}]*\}", "", head))
            self.opcode[name] = op.group(1) if op else ""
            self.comp[name] = comp
            self.body[comp].append(name)
            if is_root:
                self.root[comp] = name
            fused = _FUSED.search(head)
            if fused is not None:
                self.fused[name] = fused.group(1)
            for called in _CALLS.findall(head):
                self.callers[called].append(name)
            refs[name] = _REF.findall(head)
        self.operands = {n: [r for r in rs if r in self.scope and r != n]
                         for n, rs in refs.items()}
        self.users: Dict[str, List[str]] = defaultdict(list)
        for name, ops in self.operands.items():
            for o in ops:
                self.users[o].append(name)

    def lends(self, name: str, staged: bool) -> Optional[str]:
        """The scope ``name`` lends to a neighbour: its own, else the part
        most instructions of its fused computation carry."""
        own = self.scope[name]
        if own and self.opcode[name] not in _NO_SCOPE:
            return own if _staged(own) or not staged else None
        scopes = [self.scope[i] for i in self.body.get(
            self.fused.get(name, ""), ()) if self.scope[i]
            and self.opcode[i] not in _NO_SCOPE
            and (_staged(self.scope[i]) or not staged)]
        if not scopes:
            return None
        key = Counter(part_of(s) for s in scopes).most_common(1)[0][0]
        return next(s for s in scopes if part_of(s) == key)

    def _up(self, name: str) -> Tuple[List[str], List[str]]:
        """(producers to search on from, calling ops to look at only)."""
        if self.opcode[name] == "parameter":
            return [], self.callers[self.comp[name]]
        return self.operands[name], []

    def _down(self, name: str) -> Tuple[List[str], List[str]]:
        """(users to search on from, calling ops to look at only)."""
        if self.root.get(self.comp[name]) == name:
            return self.users[name], self.callers[self.comp[name]]
        return self.users[name], []

    def nearest(self, name: str, step, staged: bool) -> Optional[str]:
        """Breadth first along ``step``.  A loop or call op, and an op
        reached across a computation's boundary, lends its own scope but
        is not searched on from: its other operands and results carry
        other data."""
        seen = {name}
        on, only = step(name)
        queue = deque([(n, True) for n in on] + [(n, False) for n in only])
        while queue:
            n, further = queue.popleft()
            if n in seen:
                continue
            seen.add(n)
            scope = self.lends(n, staged)
            if scope:
                return scope
            if further and self.opcode[n] not in _CONTROL:
                on, only = step(n)
                queue.extend([(m, True) for m in on]
                             + [(m, False) for m in only])
        return None

    def place(self, name: str) -> Optional[str]:
        for staged in (True, False):
            scope = self.lends(name, staged) \
                or self.nearest(name, self._up, staged) \
                or self.nearest(name, self._down, staged)
            if scope:
                return scope
        return None


def fallback_scopes(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> scope path, for each instruction of a compiled
    module that has no ``op_name`` of its own and that the fallback can
    place (see the module's docstring)."""
    mod = _Module(hlo_text)
    out = {}
    for name, own in mod.scope.items():
        if own or mod.opcode[name] in _NO_SCOPE:
            continue
        scope = mod.place(name)
        if scope:
            out[name] = scope
    return out


def reduce_parts(events: Dict, fallback: Optional[Dict[str, str]] = None,
                 window: Optional[tuple] = None, top: int = 10) -> Dict:
    """Self time per part (``part_s``, seconds) and the ops that took most
    time with their part (``device_ops``), per device averaged over the
    devices, from ``tracing.load_events``'s events.  An op whose scope
    ``load_events`` could not give (its scope is its own instruction name)
    takes its scope from ``fallback``.  Self time and the window are those
    of ``tracing.reduce_events``, so without a fallback the parts of a
    stage add up to its ``stage_s``."""
    fallback = fallback or {}
    devices = events["devices"]
    if not devices:
        return {"part_s": {}, "device_ops": []}
    if window is None:
        host = events.get("host", [])
        marks = host or [o for ops in devices.values() for o in ops]
        window = (min(m["start"] for m in marks),
                  max(m["start"] + m["dur"] for m in marks))
    w0, w1 = window
    part: Dict[str, float] = defaultdict(float)
    per_op: Dict[str, float] = defaultdict(float)
    for ops in devices.values():
        ops = [dict(o, start=max(o["start"], w0),
                    dur=min(o["start"] + o["dur"], w1) - max(o["start"], w0))
               for o in ops]
        ops = [o for o in ops if o["dur"] > 0]
        for o, t in zip(ops, tracing._self_times(ops)):
            name = tracing.instruction(o["name"])
            scope = o["scope"]
            if scope == name:
                scope = fallback.get(name, scope)
            key = part_of(scope)
            part[key] += t
            per_op[f"{key}/{o['name']}"] += t
    ns = 1e-9 / len(devices)
    ops_top = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    return {"part_s": {k: v * ns for k, v in part.items()},
            "device_ops": [[k, v * ns] for k, v in ops_top]}


def per_round_ms(ctx: Dict, key: str) -> Optional[float]:
    """A part's self time per simulated round, in ms, from a per-layer
    reader's context; None where the run reduced no parts."""
    if ctx.get("unit") != "rounds" or not ctx.get("units") \
            or "part_s" not in ctx:
        return None
    return ctx["part_s"].get(key, 0.0) / ctx["units"] * 1e3
