"""The program's own spans and scopes in a profiler trace.

``trace.py`` reduces a trace to busy time, kernel time and a breakdown by
op and by host event.  This module reads what the program names itself:

* ``scope_s``: device seconds of leaf ops (``trace.py``'s rule: an op that
  encloses the next is a container) by the innermost ``jax.named_scope`` of
  the search program in the op's HLO ``op_name`` (a path component that
  starts with ``search.`` or ``hop.``); a fusion carries its root
  instruction's ``op_name``.  Ops under none sum under ``NO_SCOPE``.
* ``program_idle_s``: seconds of the idle gaps of ``trace.GAP_NS`` or more,
  each labelled with the innermost program span (a host event whose name
  starts with ``frontend.`` or ``index.``) open at its midpoint, on any host
  thread, the one started last (``trace.HostSpans``' rule, restricted to the
  program's spans).  Gaps under none sum under ``NO_SPAN``.
* ``spans``: the program spans the trace holds at all.

The plain data is ``trace.py``'s, with one addition: a device op's event
may carry a fourth element, its ``op_name``.  An event of three elements
has no scope, so a trace in the old form (the recorded fixture) reads as
ops under no scope.  ``load`` strips TraceMe metadata from host event
names (``name#id=3,rows=8#``).

A TPU trace taken without HLO protos (``chipbench/harness.py`` takes it so)
names each op by its optimized-HLO instruction and carries no ``op_name``.
So ``of_run`` gives each instruction the ``op_name`` that ``hlo_op_names``
finds in the text of the search programs the run compiled
(``engine_texts``: each one the program's engine cache holds, lowered at
the mix's buckets, which jit's own cache answers), keyed by the
instruction's name and result shape, which tell the buckets' programs
apart.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

from chipbench import trace as tr

SCOPE_PREFIXES = ("search.", "hop.")
SPAN_PREFIXES = ("frontend.", "index.")
NO_SCOPE = "no scope"
NO_SPAN = "no program span"
OP_NAME = re.compile(r'op_name="([^"]*)"')
CALLS = re.compile(r"calls=(%[\w.-]+)")
OPERAND = re.compile(r"%[\w.-]+")
INHERIT_DEPTH = 8


def strip_meta(name: str) -> str:
    """``frontend.dispatch#id=3,rows=8#`` -> ``frontend.dispatch``."""
    return name.split("#", 1)[0]


def scope_of(op_name: str) -> str:
    """The innermost program scope of an HLO ``op_name`` path."""
    for part in reversed(op_name.split("/")):
        if part.startswith(SCOPE_PREFIXES):
            return part
    return NO_SCOPE


def _head(text: str) -> tuple:
    """``%fusion.5 = f32[128]{0} fusion(...)`` -> (``%fusion.5``, ``f32[128]{0}``)."""
    name, _, rest = text.partition(" = ")
    return name.strip(), rest[:_shape_end(rest)]


def _shape_end(rest: str) -> int:
    if rest.startswith("("):                    # a tuple shape holds spaces
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                return i + 1
    return len(rest.split(" ", 1)[0])


def _operands(rest: str) -> list:
    """The operand names of an instruction's text after its ``=``."""
    i = rest.find("(", _shape_end(rest))
    depth, j = 0, i
    for j in range(i, len(rest)):
        depth += (rest[j] == "(") - (rest[j] == ")")
        if depth == 0:
            break
    return OPERAND.findall(rest[i:j])


def hlo_op_names(text: str) -> dict:
    """{(instruction, result shape): op_name} of an optimized HLO module's
    text.  A fusion takes the ``op_name`` of its fused computation's root;
    an instruction the compiler made without one (a scatter it rewrote as a
    sort and a fusion, a copy) takes that of the nearest instruction it
    consumes that has one, operands in order, nearest first."""
    shape, own, calls, ops, roots, comp = {}, {}, {}, {}, {}, None
    for line in text.splitlines():
        if not line.startswith(" "):            # a computation's header or end
            words = line.split()
            comp = (words[1] if words and words[0] == "ENTRY" and len(words) > 1
                    else words[0] if words and words[0].startswith("%") else None)
            continue
        ins = line.strip()
        root = ins.startswith("ROOT ")
        ins = ins[5:] if root else ins
        if not ins.startswith("%") or " = " not in ins:
            continue
        name, shape[name] = _head(ins)
        m = OP_NAME.search(ins)
        own[name] = m.group(1) if m else ""
        c = CALLS.search(ins)
        if c:
            calls[name] = c.group(1)
        ops[name] = _operands(ins.partition(" = ")[2])
        if root and comp:
            roots[comp] = own[name]
    direct = {n: roots.get(calls.get(n), "") or v for n, v in own.items()}

    def inherited(name):
        seen, frontier = {name}, [name]
        for _ in range(INHERIT_DEPTH):
            frontier = [o for n in frontier for o in ops.get(n, ()) if o not in seen]
            seen.update(frontier)
            for o in frontier:
                if direct.get(o):
                    return direct[o]
        return ""

    return {(n, shape[n]): direct[n] or inherited(n) for n in direct}


class OpNames:
    """A trace op's ``op_name`` from HLO module texts: by instruction name and
    result shape, else by the name alone where every module agrees."""

    def __init__(self, texts=()):
        self.by_key, names = {}, defaultdict(set)
        for text in texts:
            for key, op in hlo_op_names(text).items():
                self.by_key[key] = op
                names[key[0]].add(op)
        self.by_name = {n: ops.pop() for n, ops in names.items() if len(ops) == 1}
        self._seen = {}                         # an op's text recurs every hop

    def __call__(self, text: str) -> str:
        if text not in self._seen:
            key = _head(text)
            self._seen[text] = self.by_key.get(key, self.by_name.get(key[0], ""))
        return self._seen[text]


def engine_texts(run) -> list:
    """The optimized HLO text of each search program the program's engine
    cache holds, at each bucket of the run's mix, skipping any that cannot
    be lowered so; [] for a program without that cache."""
    import jax
    import jax.numpy as jnp

    try:
        from repro.core.search import _ENGINE_CACHE
    except ImportError:
        return []
    texts, scalar = [], jax.ShapeDtypeStruct((), jnp.float32)
    for _, _, fn in list(_ENGINE_CACHE.values()):
        for b in run.mix["buckets"]:
            q = jax.ShapeDtypeStruct((b, run.config["dim"]), jnp.float32)
            try:
                texts.append(fn._jitted.lower(fn._tables, q, scalar).compile().as_text())
            except Exception:  # noqa: BLE001 - an engine of another shape or form
                break
    return texts


def load(trace_dir: str, op_names: OpNames | None = None) -> dict:
    """The newest ``.xplane.pb`` under ``trace_dir`` as plain data, device
    ops with the ``op_name`` that ``op_names`` gives their instruction text."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    op_names = op_names or OpNames()
    planes = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        device = bool(tr.DEVICE_PLANE.match(plane.name))
        if not device and plane.name != tr.HOST_PLANE:
            continue
        lines = []
        for line in plane.lines:
            if device and line.name != tr.OPS_LINE:
                continue
            if device:
                events = [[ev.name.split(" = ", 1)[0], ev.start_ns, ev.duration_ns,
                           op_names(ev.name)] for ev in line.events]
            else:
                events = [[strip_meta(ev.name), ev.start_ns, ev.duration_ns]
                          for ev in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _program_spans(trace: dict) -> tuple:
    """(``trace.HostSpans`` over the program's spans alone, their names)."""
    lines, names = [], set()
    for p in trace["planes"]:
        if p["name"] != tr.HOST_PLANE:
            continue
        for ln in p["lines"]:
            evs = []
            for name, s, d in ln["events"]:
                if name.startswith(SPAN_PREFIXES):
                    evs.append([name, s, d])
                    names.add(name)
            lines.append({"name": ln["name"], "events": evs})
    host = tr.HostSpans({"planes": [{"name": tr.HOST_PLANE, "lines": lines}]})
    return host, names


def reduce(trace: dict) -> dict | None:
    """``scope_s``, ``program_idle_s`` (both means over devices) and
    ``spans``; None where the trace holds no device op."""
    ops = {k: v for k, v in tr._device_ops(trace).items() if v}
    if not ops:
        return None
    host, names = _program_spans(trace)
    scope_s, idle = defaultdict(float), defaultdict(float)
    for events in ops.values():
        ordered = sorted(events, key=lambda ev: (ev[1], -ev[2]))
        for i, ev in enumerate(ordered):
            if i + 1 < len(ordered) and ordered[i + 1][1] < ev[1] + ev[2]:
                continue            # encloses the next op: a container
            scope_s[scope_of(ev[3] if len(ev) > 3 else "")] += ev[2] * 1e-9
        merged = tr.union([(s, s + d) for _, s, d, *_ in events])
        for (_, e0), (s1, _) in zip(merged, merged[1:]):
            if s1 - e0 >= tr.GAP_NS:
                label = host.label((e0 + s1) / 2)
                idle[NO_SPAN if label == "no host span" else label] += (s1 - e0) * 1e-9
    n = len(ops)
    return {"scope_s": {k: v / n for k, v in scope_s.items()},
            "program_idle_s": {k: v / n for k, v in idle.items()},
            "spans": sorted(names)}


def of_run(run):
    """The reduction of a traced run's trace, read from the harness's trace
    directory once per run; None where the run was not traced."""
    if run.trace is None:
        return None
    if not hasattr(run, "program_trace"):
        from chipbench.harness import TRACE_DIR

        run.program_trace = reduce(load(str(TRACE_DIR), OpNames(engine_texts(run))))
    return run.program_trace
