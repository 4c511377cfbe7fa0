"""A second reduction of the same ``.xplane.pb``: what the PROGRAM says
about itself. ``reduce.py`` reads names XLA and PJRT chose (``copy.68``,
``np.asarray(jax.Array)``); this reads the names ray_tpu chose:

  * the ``rt.*`` host spans (``observability/tracing.py step_span``) with
    their attributes, as far as they lie inside the traced window, and the
    window's idle seconds by the innermost ``rt.*`` span that covers them;
  * device seconds by ``jax.named_scope`` name. A device event carries only
    XLA's instruction name, so the scope comes from the compiled program
    the trace holds in its ``/host:metadata`` plane: the instruction's own
    ``op_name``; for a fusion without one, the scope most of its fused
    instructions carry; for an instruction the compiler inserted without
    metadata (a copy, the done half of an asynchronous copy), the scope of
    the operand it moves. What is still without a scope is ``unscoped`` and
    listed by name.

A trace of a program that has neither spans nor scopes (the parent of the
PR that added them) reduces to empty tables, never to an error.

The trace is parsed with ``google.protobuf`` against the few fields of the
XSpace and HloProto messages that are read here (``ProfileData`` does not
show an event's metadata stats, where the program id is, nor the HLO).
"""

from __future__ import annotations

import functools
import re
from collections import Counter
from typing import Dict, Iterable, List, Optional, Tuple

from benchmark.trace.reduce import (CONTAINERS, DEVICE_PLANE, HOST_PLANE,
                                    OPS_LINE, length, parse_op, subtract,
                                    union)

# Every scope name the programs carry (models/llama.py, llm/engine.py,
# train/step.py, models/gpt2.py). An operation belongs to the innermost.
SCOPES = ("layers", "kv_gather", "kv_write", "attn", "qkv", "mlp", "embed",
          "lm_head", "sample", "prefill_lane", "fwd_bwd", "optimizer",
          "grad_norm", "ce")
CARRY = "layers.carry"  # under ``layers`` and no inner scope: the scan's own
UNSCOPED = "unscoped"
KV_MOVE = ("kv_gather", "kv_write", CARRY)
SPAN_PREFIX = "rt."
_TOKEN = re.compile(r"[A-Za-z_][A-Za-z_0-9]*")
_PROGRAM_ID = re.compile(r"\((\d+)\)$")
# operands looked through when an instruction inherits its operand's scope
_INHERIT_DEPTH = 4


# -- the two protobuf schemas, as far as they are read ------------------------

@functools.lru_cache(maxsize=None)
def _messages():
    """Message classes for XSpace and HloProto built from the field
    numbers this module reads; every other field is skipped on parse."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    F = descriptor_pb2.FieldDescriptorProto
    kinds = {"int64": F.TYPE_INT64, "uint64": F.TYPE_UINT64,
             "double": F.TYPE_DOUBLE, "string": F.TYPE_STRING,
             "bytes": F.TYPE_BYTES}
    schema = {
        "XSpace": [("planes", 1, "XPlane", True)],
        "XPlane": [("name", 2, "string", False), ("lines", 3, "XLine", True),
                   ("event_metadata", 4, "EventMetaEntry", True),
                   ("stat_metadata", 5, "StatMetaEntry", True)],
        "EventMetaEntry": [("key", 1, "int64", False),
                           ("value", 2, "XEventMetadata", False)],
        "StatMetaEntry": [("key", 1, "int64", False),
                          ("value", 2, "XStatMetadata", False)],
        "XLine": [("name", 2, "string", False),
                  ("timestamp_ns", 3, "int64", False),
                  ("events", 4, "XEvent", True)],
        "XEvent": [("metadata_id", 1, "int64", False),
                   ("offset_ps", 2, "int64", False),
                   ("duration_ps", 3, "int64", False),
                   ("stats", 4, "XStat", True)],
        "XStat": [("metadata_id", 1, "int64", False),
                  ("double_value", 2, "double", False),
                  ("uint64_value", 3, "uint64", False),
                  ("int64_value", 4, "int64", False),
                  ("str_value", 5, "string", False),
                  ("bytes_value", 6, "bytes", False),
                  ("ref_value", 7, "uint64", False)],
        "XEventMetadata": [("id", 1, "int64", False),
                           ("name", 2, "string", False),
                           ("stats", 5, "XStat", True)],
        "XStatMetadata": [("id", 1, "int64", False),
                          ("name", 2, "string", False)],
        "HloProto": [("hlo_module", 1, "HloModule", False)],
        "HloModule": [("name", 1, "string", False),
                      ("computations", 3, "HloComputation", True)],
        "HloComputation": [("name", 1, "string", False),
                           ("instructions", 2, "HloInstruction", True),
                           ("id", 5, "int64", False)],
        "HloInstruction": [("name", 1, "string", False),
                           ("opcode", 2, "string", False),
                           ("metadata", 7, "OpMetadata", False),
                           ("id", 35, "int64", False),
                           ("operand_ids", 36, "int64", True),
                           ("called_computation_ids", 38, "int64", True)],
        "OpMetadata": [("op_name", 2, "string", False)],
    }
    fd = descriptor_pb2.FileDescriptorProto(
        name="benchmark_trace_program.proto", package="rtbench",
        syntax="proto3")
    for msg, fields in schema.items():
        m = fd.message_type.add(name=msg)
        for name, number, kind, repeated in fields:
            f = m.field.add(name=name, number=number,
                            label=F.LABEL_REPEATED if repeated
                            else F.LABEL_OPTIONAL)
            if kind in kinds:
                f.type = kinds[kind]
            else:
                f.type, f.type_name = F.TYPE_MESSAGE, f".rtbench.{kind}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return {msg: message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"rtbench.{msg}")) for msg in schema}


# -- scopes of one compiled program -------------------------------------------

def scope_of(op_name: str) -> Tuple[Optional[str], str]:
    """``(scope, phase)`` of an HLO ``op_name``. The scope is the last of
    :data:`SCOPES` among the name's words (JAX writes a scope inside its
    own wrappers too: ``fwd_bwd/transpose(jvp(ce))/while/body/...``); the
    last path component is the primitive and is left out. The phase says
    which half of a differentiated program the operation is in:
    ``backward`` under a ``transpose(...)`` (the recomputation a remat
    policy asks for runs there), else ``forward``."""
    path, _, _ = op_name.rpartition("/")
    words = _TOKEN.findall(path)
    scope = next((w for w in reversed(words) if w in SCOPES), None)
    return scope, "backward" if "transpose" in words else "forward"


def program_scopes(module) -> Dict[str, Tuple[str, str, str]]:
    """``{instruction name: (scope, phase, rule)}`` for every instruction
    of a parsed HloModule that has a scope; ``rule`` is how it was found:
    ``own``, ``fused`` or ``operand``."""
    by_id, comps = {}, {}
    for comp in module.computations:
        comps[comp.id] = comp
        for ins in comp.instructions:
            by_id[ins.id] = ins
    found: Dict[int, Optional[Tuple[str, str, str]]] = {}

    def own(ins):
        if ins.metadata.op_name:
            scope, phase = scope_of(ins.metadata.op_name)
            if scope:
                return scope, phase, "own"
        return None

    def fused(ins, depth=0):
        votes: Counter = Counter()
        for cid in ins.called_computation_ids:
            for inner in comps[cid].instructions if cid in comps else ():
                got = own(inner) or (fused(inner, depth + 1)
                                     if depth < 2 else None)
                if got:
                    votes[got[:2]] += 1
        if not votes:
            return None
        (scope, phase), _ = votes.most_common(1)[0]
        return scope, phase, "fused"

    def resolve(ins, depth=0):
        if ins.id in found:
            return found[ins.id]
        got = own(ins)
        if got is None and ins.opcode == "fusion":
            got = fused(ins)
        if got is None and depth < _INHERIT_DEPTH:
            for oid in ins.operand_ids:
                operand = by_id.get(oid)
                up = resolve(operand, depth + 1) if operand else None
                if up:
                    got = (up[0], up[1], "operand")
                    break
        if depth == 0:
            found[ins.id] = got
        return got

    out = {}
    for ins in by_id.values():
        got = resolve(ins)
        if got:
            out[ins.name] = got
    return out


# -- load ----------------------------------------------------------------------

def load(path: str) -> dict:
    """The neutral form this reduction works on:

        {"spans": [(name, thread, start_ns, duration_ns, {attr: value})],
         "devices": [{"name": plane, "ops": [(text, program_id, start_ns,
                                               duration_ns)]}],
         "programs": {program_id: {instruction: (scope, phase, rule)}},
         "window": (first start_ns, last end_ns) over every event of the
                   device and host planes, as ``reduce.reduce`` takes it}
    """
    import gzip

    with (gzip.open if path.endswith(".gz") else open)(path, "rb") as fh:
        return load_bytes(fh.read())


def load_bytes(raw: bytes) -> dict:
    cls = _messages()
    space = cls["XSpace"]()
    space.ParseFromString(raw)
    spans, devices, programs = [], [], {}
    t_lo, t_hi = float("inf"), float("-inf")
    for plane in space.planes:
        if plane.name == HOST_PLANE or DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                for ev in line.events:
                    start = line.timestamp_ns + ev.offset_ps * 1e-3
                    t_lo = min(t_lo, start)
                    t_hi = max(t_hi, start + ev.duration_ps * 1e-3)
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {e.key: e.value for e in plane.event_metadata}

        def value(stat):
            which = [f.name for f, _ in stat.ListFields()
                     if f.name != "metadata_id"]
            if not which:
                return 0
            if which[0] == "ref_value":
                return stat_names.get(stat.ref_value, "")
            return getattr(stat, which[0])

        if plane.name == "/host:metadata":
            for m in meta.values():
                pid = _PROGRAM_ID.search(m.name)
                for stat in m.stats:
                    if stat.bytes_value and pid:
                        proto = cls["HloProto"]()
                        proto.ParseFromString(stat.bytes_value)
                        programs[int(pid.group(1))] = program_scopes(
                            proto.hlo_module)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                thread = line.name.split("/")[0]
                for ev in line.events:
                    name = meta[ev.metadata_id].name
                    if not name.startswith(SPAN_PREFIX):
                        continue
                    attrs = {stat_names.get(s.metadata_id, "?"): value(s)
                             for s in ev.stats}
                    spans.append((name, thread,
                                  line.timestamp_ns + ev.offset_ps * 1e-3,
                                  ev.duration_ps * 1e-3, attrs))
        elif DEVICE_PLANE.match(plane.name):
            ops = []
            pid_of = {}
            for key, m in meta.items():
                for stat in m.stats:
                    if stat_names.get(stat.metadata_id) == "program_id":
                        pid_of[key] = int(value(stat))
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    ops.append((meta[ev.metadata_id].name,
                                pid_of.get(ev.metadata_id, 0),
                                line.timestamp_ns + ev.offset_ps * 1e-3,
                                ev.duration_ps * 1e-3))
            devices.append({"name": plane.name, "ops": ops})
    return {"spans": spans, "devices": devices, "programs": programs,
            "window": (t_lo, t_hi)}


# -- the reduction ---------------------------------------------------------------

def _innermost(spans: List[tuple], lo: float, hi: float) -> str:
    """The shortest ``rt.*`` span that covers the middle of [lo, hi]."""
    mid, best, best_len = (lo + hi) / 2, "host idle", None
    for name, _, s, d, _ in spans:
        if s <= mid <= s + d and (best_len is None or d < best_len):
            best, best_len = name, d
    return best


def reduce(space: dict, top: int = 20) -> dict:
    """``window_s`` and ``busy_s`` as ``reduce.reduce`` defines them (all
    device operations, loop containers left out; averages over devices),
    so shares read here divide by the same denominators."""
    devices, spans = space["devices"], space["spans"]
    if not devices:
        raise ValueError("the trace has no device plane")
    scopes: Counter = Counter()
    phases: Counter = Counter()
    rules: Counter = Counter()
    unscoped: Counter = Counter()
    busy, first_busy = [], []
    t_lo, t_hi = space["window"]
    parsed: Dict[str, dict] = {}
    for dev in devices:
        intervals = []
        for text, pid, s, d in dev["ops"]:
            op = parsed.get(text)
            if op is None:
                op = parsed[text] = parse_op(text)
            if op["opcode"] in CONTAINERS:
                continue
            intervals.append((s, s + d))
            got = space["programs"].get(pid, {}).get(op["short"])
            if got is None:
                scope, phase, rule = UNSCOPED, "none", "none"
                unscoped[op["short"]] += d * 1e-9
            else:
                scope, phase, rule = got
                scope = CARRY if scope == "layers" else scope
            scopes[scope] += d * 1e-9
            phases[(scope, phase)] += d * 1e-9
            rules[rule] += d * 1e-9
        u = union(intervals)
        busy.append(length(u) * 1e-9)
        if dev is devices[0]:
            first_busy = u
    n = len(devices)
    # host spans inside the traced window, and the idle time under them
    inside = [sp for sp in spans if sp[2] >= t_lo and sp[2] + sp[3] <= t_hi]
    idle: Counter = Counter()
    for lo, hi in subtract([(t_lo, t_hi)], first_busy):
        idle[_innermost(spans, lo, hi)] += (hi - lo) * 1e-9
    return {
        "window_s": (t_hi - t_lo) * 1e-9,
        "busy_s": sum(busy) / n if n else 0.0,
        "scopes": {k: v / n for k, v in scopes.items()},
        "scope_phases": {f"{s}:{p}": v / n for (s, p), v in phases.items()},
        "scope_rules": {k: v / n for k, v in rules.items()},
        "unscoped_ops": [[k, v / n] for k, v in unscoped.most_common(top)],
        "spans": [{"name": name, "thread": thread,
                   "start_s": (s - t_lo) * 1e-9, "duration_s": d * 1e-9,
                   "attrs": attrs}
                  for name, thread, s, d, attrs in inside],
        "idle_by_span": dict(idle),
    }


# -- what the readers share --------------------------------------------------------

def program_of(ctx: dict) -> Optional[dict]:
    """The reduction a reader works on: ``ctx["trace"]["program"]``, or
    None where the run has no trace or its reduction no such key."""
    return (ctx.get("trace") or {}).get("program")


def spans_named(program: dict, name: str, where: Optional[dict] = None
                ) -> Iterable[dict]:
    for sp in program.get("spans", ()):
        if sp["name"] == name and all(
                sp["attrs"].get(k) == v for k, v in (where or {}).items()):
            yield sp
