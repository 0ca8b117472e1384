"""From a profiler trace to the benchmark's device numbers.

Two stages, so that the arithmetic can be checked without a chip:

* ``load_events(xplane_path)`` reads an ``.xplane.pb`` with
  ``jax.profiler.ProfileData`` and returns plain dicts: the device
  operations of every TPU plane (line "XLA Ops") and the host spans the
  benchmark's trainers wrote (``jax.profiler.TraceAnnotation`` names
  that start with ``bench/``);
* ``reduce(events)`` is pure Python over those dicts: busy union, idle
  share, self time by category and by module path, idle gaps attributed
  to the host span that covers most of each.

What the v5e's trace holds (looked at by hand, PR 22): an operation's
event is named by the text of its optimized HLO instruction
(``%fusion.12 = bf16[..] fusion(..), kind=kOutput, calls=..``) and
carries no category and no source path.  So the category is read from
that text (``parse_instruction``), and the module path comes from the
compiled program's own text, whose instructions carry
``metadata={op_name=".."}``: ``op_names(hlo_text)`` maps instruction
names to paths and ``load_events`` joins them in.

Times are nanoseconds on the trace's own clock; the profiler puts host
and device planes on one clock.
"""

import bisect
import re
from typing import Dict, Iterable, List, Optional, Tuple

SPAN_PREFIX = "bench/"
WINDOW_SPAN = SPAN_PREFIX + "window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ATTENTION_PROJECTIONS = ("query", "key", "value", "out")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
# ``%name = shape opcode(``: opcodes are lower case, layout notes such
# as ``T(8,128)`` and ``S(1)`` upper case.
_INSTRUCTION = re.compile(r"^%?([\w.\-]+) = .*?(?<![\w.%])([a-z][a-z0-9\-]*)\(")
_FUSION_KIND = re.compile(r"kind=k(\w+)")
_OP_NAME = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = .*metadata=\{[^}]*op_name=\"([^\"]+)\"")

Interval = Tuple[float, float]


# -- stage 1: the trace file ------------------------------------------------

def parse_instruction(text: str) -> Tuple[str, str]:
    """``(name, category)`` of an operation from the HLO text the trace
    names it by.  XLA's TPU backend lowers ``dot_general`` to a
    convolution and fuses around it as an output fusion, so those are
    the matrix unit's operations."""
    match = _INSTRUCTION.match(text)
    if not match:
        return text.split(" ")[0].lstrip("%"), "other"
    name, opcode = match.groups()
    if opcode == "fusion":
        kind = _FUSION_KIND.search(text)
        kind = kind.group(1).lower() if kind else "unknown"
        if kind == "output" or "convolution" in name:
            return name, "mxu fusion"
        return name, {"loop": "loop fusion",
                      "input": "reduce fusion"}.get(kind, kind + " fusion")
    if opcode in ("convolution", "dot"):
        return name, "mxu"
    if opcode.startswith(COLLECTIVES):
        return name, "collective"
    return name, opcode


def op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> ``op_name`` of its metadata, from a compiled
    program's text (``compiled.as_text()``)."""
    out = {}
    for line in hlo_text.splitlines():
        match = _OP_NAME.match(line)
        if match:
            out[match.group(1)] = match.group(2)
    return out


def load_events(xplane_path: str,
                names: Optional[Dict[str, str]] = None) -> dict:
    """``names``: what ``op_names`` gave for the traced programs."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    names = names or {}
    device, host = [], []
    for plane in data.planes:
        match = DEVICE_PLANE.match(plane.name)
        if match:
            lines = {line.name: line for line in plane.lines}
            # ``jit__step(3716328842643565401)``: the program an
            # operation ran in, by the interval that holds it.
            modules = sorted(
                (float(m.start_ns), float(m.start_ns + m.duration_ns),
                 m.name.split("(")[0])
                for m in (lines[MODULES_LINE].events
                          if MODULES_LINE in lines else ()))
            starts = [m[0] for m in modules]
            for e in (lines[OPS_LINE].events if OPS_LINE in lines else ()):
                name, category = parse_instruction(e.name)
                ev = {"chip": int(match.group(1)), "name": name,
                      "category": category,
                      "start_ns": float(e.start_ns),
                      "dur_ns": float(e.duration_ns)}
                at = bisect.bisect_right(starts, ev["start_ns"]) - 1
                if at >= 0 and ev["start_ns"] < modules[at][1]:
                    ev["module"] = modules[at][2]
                if name in names:
                    ev["op_name"] = names[name]
                device.append(ev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        host.append({"name": e.name,
                                     "start_ns": float(e.start_ns),
                                     "dur_ns": float(e.duration_ns)})
    return {"device": device, "host": host}


def describe(xplane_path: str, samples: int = 3) -> List[str]:
    """Planes, lines, event counts and a few events with every stat:
    what to look at by hand before trusting a reduction."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(xplane_path).planes:
        out.append("plane %s" % plane.name)
        for line in plane.lines:
            events = list(line.events)
            out.append("  line %s: %d events" % (line.name, len(events)))
            for e in events[:samples]:
                out.append("    %s start %.0f dur %.0f %s" % (
                    e.name[:300], e.start_ns, e.duration_ns,
                    {k: str(v)[:120] for k, v in dict(e.stats).items()}))
    return out


# -- stage 2: arithmetic on plain events --------------------------------------

def _end(ev: dict) -> float:
    return ev["start_ns"] + ev["dur_ns"]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same points."""
    out: List[List[float]] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def gaps(busy: List[Interval], window: Interval) -> List[Interval]:
    """The parts of ``window`` that the disjoint, sorted ``busy`` leaves."""
    out, at = [], window[0]
    for a, b in busy:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if window[1] > at:
        out.append((at, window[1]))
    return out


def self_times(events: List[dict]) -> List[float]:
    """Each event's duration less what events nested in it cover (a
    ``while`` on the ops line holds its body's operations).  Events of
    one chip nest or follow one another, they do not cross."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i]["start_ns"],
                                  -events[i]["dur_ns"]))
    own = [ev["dur_ns"] for ev in events]
    stack: List[int] = []
    for i in order:
        while stack and _end(events[stack[-1]]) <= events[i]["start_ns"]:
            stack.pop()
        if stack:
            own[stack[-1]] -= events[i]["dur_ns"]
        stack.append(i)
    return [max(t, 0.0) for t in own]


def module_path(ev: dict) -> Optional[str]:
    """The Flax module path in the operation's metadata, without the
    ``jit(...)`` frames and the primitive at its end; None where the
    trace carries no path."""
    op = ev.get("op_name")
    if not op:
        return None
    parts = [p for p in str(op).split("/")
             if p and "(" not in p and not p.endswith(":")]
    return "/".join(parts[:-1]) or None


def group_key(ev: dict) -> str:
    """The name under which the breakdown adds an operation up: its
    module path with the layer number starred out and its category;
    where the trace has no path, its program and its own name without
    XLA's numbering."""
    path = module_path(ev)
    if path:
        return "%s [%s]" % (re.sub(r"layer_\d+", "layer_*", path),
                            ev.get("category", "other"))
    name = re.sub(r"[.\d]+$", "", ev["name"])
    return "%s/%s" % (ev["module"], name) if ev.get("module") else name


def is_mxu(ev: dict) -> bool:
    return str(ev.get("category", "")).startswith("mxu")


def is_attention_core(ev: dict) -> bool:
    """Under an ``attention`` module, and not one of its projections."""
    path = module_path(ev)
    if not path:
        return False
    parts = path.split("/")
    if "attention" not in parts:
        return False
    after = parts[parts.index("attention") + 1:]
    return not (after and after[0] in ATTENTION_PROJECTIONS)


def attribute(gap: Interval, spans: List[dict]) -> str:
    """The host span that covers most of ``gap`` (the shortest of those
    that tie, so the innermost), or ``host/none``."""
    best, best_cover, best_len = "host/none", 0.0, 0.0
    for s in spans:
        cover = min(gap[1], _end(s)) - max(gap[0], s["start_ns"])
        if cover > best_cover or (cover == best_cover and cover > 0
                                  and s["dur_ns"] < best_len):
            best, best_cover, best_len = s["name"], cover, s["dur_ns"]
    return best


def reduce(events: dict, top: int = 10) -> Optional[dict]:
    """The numbers of one traced window, or None when the trace holds
    no device operation.  The window is the ``bench/window`` host span;
    where the trace has none, the extent of the device operations."""
    device = events["device"]
    if not device:
        return None
    chips = sorted({ev["chip"] for ev in device})
    window_spans = [s for s in events["host"] if s["name"] == WINDOW_SPAN]
    if window_spans:
        w = max(window_spans, key=lambda s: s["dur_ns"])
        window, window_source = (w["start_ns"], _end(w)), "host_span"
    else:
        window = (min(ev["start_ns"] for ev in device),
                  max(_end(ev) for ev in device))
        window_source = "device_extent"
    inside = [ev for ev in device
              if _end(ev) > window[0] and ev["start_ns"] < window[1]]
    if not inside:
        return None
    spans = [s for s in events["host"] if s["name"] != WINDOW_SPAN]

    busy_by_chip = {}
    for chip in chips:
        busy_by_chip[chip] = union(clip(
            ((ev["start_ns"], _end(ev)) for ev in inside
             if ev["chip"] == chip), window))
    busy_ns = sum(total(b) for b in busy_by_chip.values()) / len(chips)
    window_ns = window[1] - window[0]

    by_group: Dict[str, float] = {}
    mxu_ns = attention_ns = collective_ns = self_ns = 0.0
    has_path = False
    for chip in chips:
        of_chip = [ev for ev in inside if ev["chip"] == chip]
        for ev, own in zip(of_chip, self_times(of_chip)):
            self_ns += own
            key = group_key(ev)
            by_group[key] = by_group.get(key, 0.0) + own
            if is_mxu(ev):
                mxu_ns += own
            if ev.get("category") == "collective":
                collective_ns += own
            if module_path(ev):
                has_path = True
                if is_attention_core(ev):
                    attention_ns += own

    first = busy_by_chip[chips[0]]
    idle = gaps(first, window)
    by_span: Dict[str, float] = {}
    for gap in idle:
        name = attribute(gap, spans)
        by_span[name] = by_span.get(name, 0.0) + (gap[1] - gap[0])
    longest = sorted(idle, key=lambda g: g[0] - g[1])[:5]

    def ranked(d, over=1):
        return [[k, v / 1e9 / over] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {
        "chips": len(chips),
        "window_source": window_source,
        "window_s": window_ns / 1e9,
        "busy_s": busy_ns / 1e9,
        "idle_share": 1.0 - total(first) / window_ns,
        "self_s": self_ns / 1e9 / len(chips),
        "mxu_share": mxu_ns / self_ns if self_ns else None,
        "collective_s": collective_ns / 1e9 / len(chips),
        "attention_share": (attention_ns / self_ns
                            if has_path and self_ns else None),
        "device_ops": ranked(by_group, len(chips)),   # a chip's average
        "idle_gaps": ranked(by_span),
        "longest_gaps": [[attribute(g, spans), (g[1] - g[0]) / 1e9]
                         for g in longest],
        "events": len(inside),
    }
