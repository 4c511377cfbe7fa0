"""From the profiler's ``.xplane.pb`` to numbers: device busy and idle
time, a table of device operations, per-program execution times, kernel
calls with their shapes, collective time that no compute hides, and the
longest idle gaps with what the host was doing in each.

The trace is first turned into a neutral form (``load_xplane``) so that the
arithmetic (``reduce``) can be checked on a hand-made trace as well as on a
recorded one:

    {"planes": [{"name": str, "lines": [{"name": str,
        "events": [(name, start_ns, duration_ns), ...]}]}]}

How a TPU trace is laid out (read off a recorded one, jax 0.9, v5e): one
plane ``/device:TPU:<n>`` per chip with the lines ``XLA Modules`` (one
event per executed program, named ``jit_<fn>(<fingerprint>)``), ``XLA Ops``
(one event per executed HLO operation, named by its whole HLO text
``%name = shape opcode(operands), attributes``; the core runs them one
after another) and ``Async XLA Ops`` (the span from an asynchronous
operation's start to its done); one plane ``/host:CPU`` with a line per
host thread. Host and device events share a clock to within about half a
millisecond.
"""

from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE, MODULES_LINE, ASYNC_LINE = "XLA Ops", "XLA Modules", "Async XLA Ops"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")
_OPCODE = re.compile(r"(?:^| )([a-z][a-z0-9\-]*)\(")
_SHAPE = re.compile(r"\b(pred|[a-z]+[0-9]+[a-z0-9]*)\[([0-9,]*)\]")

Interval = Tuple[float, float]


def load_xplane(path: str) -> dict:
    """The neutral form of a recorded trace, read with JAX alone."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        if not (DEVICE_PLANE.match(plane.name) or plane.name == HOST_PLANE):
            continue
        lines = [{"name": line.name,
                  "events": [(ev.name, float(ev.start_ns),
                              float(ev.duration_ns)) for ev in line.events]}
                 for line in plane.lines]
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


# -- interval arithmetic ------------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def length(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """The part of union ``a`` that union ``b`` does not cover."""
    out, j = [], 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k, cur = j, lo
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


# -- HLO text -------------------------------------------------------------------

def parse_op(text: str) -> dict:
    """``%name = shape opcode(operands), attrs`` -> its parts. A name that
    is not HLO text is its own short name with opcode ``""``."""
    short, _, rest = text.partition(" = ")
    if not rest:
        return {"short": text, "opcode": "", "outputs": [], "operands": [],
                "text": text}
    m = _OPCODE.search(rest)
    opcode = m.group(1) if m else ""
    head = rest[:m.start(1)] if m else rest
    tail = rest[m.end() - 1:] if m else ""
    # operands end at the parenthesis that closes the opcode's own
    depth, end = 0, len(tail)
    for i, ch in enumerate(tail):
        depth += ch == "("
        depth -= ch == ")"
        if depth == 0 and i > 0:
            end = i
            break
    shapes = lambda s: [(d, tuple(int(x) for x in dims.split(",") if x))
                        for d, dims in _SHAPE.findall(s)]
    return {"short": short.lstrip("%"), "opcode": opcode,
            "outputs": shapes(head), "operands": shapes(tail[:end]),
            "text": text}


CONTAINERS = ("while", "conditional", "call")


def is_collective(opcode: str) -> bool:
    return any(opcode == c or opcode in (c + "-start", c + "-done")
               for c in COLLECTIVES)


def is_compute(op: dict) -> bool:
    """An operation that keeps the core's arithmetic or memory units busy
    with the program's own work: not a collective, and not the start or
    done marker of an asynchronous operation."""
    oc = op["opcode"]
    return not (is_collective(oc) or oc.endswith("-start")
                or oc.endswith("-done"))


def is_kernel(op: dict) -> bool:
    return op["opcode"] == "custom-call" and "tpu_custom_call" in op["text"]


# -- the reduction ----------------------------------------------------------------

def _label(op: dict) -> str:
    shape = ""
    if op["outputs"]:
        d, dims = op["outputs"][0]
        shape = f" {d}[{','.join(map(str, dims))}]"
    return f"{op['short']} {op['opcode']}{shape}"[:120]


def reduce(space: dict, top: int = 10) -> dict:
    """See the module's docstring. Times in seconds; shares are left to
    the readers. ``busy_s``, ``exposed_collective_s`` and ``collective_s``
    are averages over the device planes."""
    devices = [p for p in space["planes"] if DEVICE_PLANE.match(p["name"])]
    host = [p for p in space["planes"] if p["name"] == HOST_PLANE]
    if not devices:
        raise ValueError("the trace has no device plane: nothing ran on "
                         "an accelerator while it was recorded")
    starts, ends = [], []
    for plane in devices + host:
        for line in plane["lines"]:
            for _, s, d in line["events"]:
                starts.append(s)
                ends.append(s + d)
    t_lo, t_hi = min(starts), max(ends)
    op_table: Dict[str, list] = {}
    modules: Dict[str, list] = {}
    kernels: Dict[str, dict] = {}
    busy, exposed, coll_total, parsed = [], [], [], {}
    gap_candidates: List[Interval] = []
    for plane in devices:
        lines = {l["name"]: l["events"] for l in plane["lines"]}
        compute, coll, all_ops = [], [], []
        for name, s, d in lines.get(OPS_LINE, []):
            op = parsed.get(name)
            if op is None:
                op = parsed[name] = parse_op(name)
            if op["opcode"] in CONTAINERS:
                # a loop's event spans the operations of its body, which
                # have events of their own: counting it would count them
                # twice and hide every gap and collective inside it
                continue
            iv = (s, s + d)
            all_ops.append(iv)
            (coll if is_collective(op["opcode"]) else
             compute if is_compute(op) else []).append(iv)
            row = op_table.setdefault(op["short"], [_label(op), 0, 0.0])
            row[1] += 1
            row[2] += d * 1e-9
            if is_kernel(op):
                sig = repr((op["outputs"], op["operands"]))
                k = kernels.setdefault(sig, {
                    "outputs": op["outputs"], "operands": op["operands"],
                    "short": op["short"], "calls": 0, "seconds": 0.0})
                k["calls"] += 1
                k["seconds"] += d * 1e-9
        for name, s, d in lines.get(ASYNC_LINE, []):
            op = parsed.get(name)
            if op is None:
                op = parsed[name] = parse_op(name)
            if is_collective(op["opcode"]):
                coll.append((s, s + d))
        for name, s, d in lines.get(MODULES_LINE, []):
            modules.setdefault(name.split("(")[0], []).append(d * 1e-9)
        ops_u, comp_u, coll_u = union(all_ops), union(compute), union(coll)
        busy.append(length(ops_u) * 1e-9)
        coll_total.append(length(coll_u) * 1e-9)
        exposed.append(length(subtract(coll_u, comp_u)) * 1e-9)
        if plane is devices[0]:
            gap_candidates = subtract([(t_lo, t_hi)], ops_u)
    n = len(devices)
    ranked = sorted(op_table.values(), key=lambda r: -r[2])
    return {
        "devices": n,
        "window_s": (t_hi - t_lo) * 1e-9,
        "busy_s": sum(busy) / n,
        "busy_s_per_device": busy,
        "collective_s": sum(coll_total) / n,
        "exposed_collective_s": sum(exposed) / n,
        "modules": {k: v for k, v in modules.items()},
        "kernels": list(kernels.values()),
        "ops": [[label, calls, secs / n] for label, calls, secs in ranked[:200]],
        "device_ops": [[label, secs / n] for label, _, secs in ranked[:top]],
        "idle_gaps": idle_gaps(gap_candidates, host, top),
    }


def idle_gaps(gaps: List[Interval], host_planes: list, top: int) -> list:
    """The ``top`` longest gaps of the first device, each named by what the
    host was doing: the shortest host event that covers at least half of
    the gap, else the one that overlaps it most, as ``thread: event``;
    ``host idle`` where no host event overlaps it at all."""
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    events = [(s, s + d, f"{line['name'].split('/')[0]}: {name}"[:120])
              for plane in host_planes for line in plane["lines"]
              for name, s, d in line["events"] if d > 0]
    out = []
    for lo, hi in longest:
        best, best_key = "host idle", None
        for s, e, label in events:
            ov = min(e, hi) - max(s, lo)
            if ov <= 0:
                continue
            covers = ov >= 0.5 * (hi - lo)
            key = (covers, -(e - s) if covers else ov)
            if best_key is None or key > best_key:
                best, best_key = label, key
        out.append([best, (hi - lo) * 1e-9])
    return out
