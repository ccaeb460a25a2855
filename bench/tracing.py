"""Reduces a profiler trace of the timed window to what the per-layer
readers need: per device, the busy time, each layer's op time, the
collectives' time and its exposed part; on the host, the harness's spans;
and the breakdown (top device ops, longest idle gaps by host span).

Device ops are the events of each device's "XLA Ops" line. An op belongs to
a layer by its HLO instruction in the compiled step (``hlo_layers``):
collectives are "exchange"; ops whose jax name stack lies under the step
but outside its ``vmap`` (the per-pod value-and-grad) are "update", the
packed elastic update and its pack and unpack; every other op of the step
is "fwd_bwd". An op outside the step's module spans is "other". Collective
time is the union of the collectives' async spans ("Async XLA Ops") and
their synchronous ops; its exposed part is what no other op overlaps.
"""
from __future__ import annotations

import re
from collections import defaultdict

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
HOST_SPANS = ("bench.input", "bench.dispatch", "bench.loss_read")
STEP_SPAN = "bench.step"

_INSTR = re.compile(r"\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
_CALLS = re.compile(r"(?:calls|to_apply|body|condition)=%([\w.\-]+)")
_OPNAME = re.compile(r'op_name="([^"]*)"')
_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")


def _opcode(rest: str) -> str:
    """The opcode of an instruction's right-hand side: skip the shape (a
    token, or a parenthesised tuple), then the word before '('."""
    i = 0
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        i += 1
    else:
        i = rest.find(" ")
    m = re.match(r"\s*([\w\-]+)\(", rest[i:])
    return m.group(1) if m else ""


def parse_hlo(text: str) -> dict:
    """instruction name -> (opcode, op_name or None, called computations)
    for every instruction of a compiled module's text; an instruction
    without an op_name takes that of the computation it calls."""
    instrs, comps, current = {}, defaultdict(list), None
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m and current is not None:
            name, rest = m.groups()
            op = _OPNAME.search(rest)
            instrs[name] = [_opcode(rest), op.group(1) if op else None,
                            _CALLS.findall(rest)]
            comps[current].append(name)
            continue
        m = _COMP.match(line)
        if m and "=" not in line.split("{")[0].split("(")[0]:
            current = m.group(1)

    def resolve(name, seen):
        opcode, op_name, calls = instrs[name]
        if op_name is None:
            for comp in calls:
                if comp in seen:
                    continue
                seen.add(comp)
                for inner in reversed(comps.get(comp, [])):
                    op_name = resolve(inner, seen)
                    if op_name:
                        break
                if op_name:
                    break
            instrs[name][1] = op_name
        return op_name

    for name in instrs:
        resolve(name, set())
    return {n: (v[0], v[1]) for n, v in instrs.items()}


def layer_of(opcode: str, op_name: str | None, step_name: str) -> str:
    base = re.sub(r"-(start|done)$", "", opcode)
    if base in COLLECTIVES:
        return "exchange"
    prefix = f"jit({step_name})/"
    if op_name and op_name.startswith(prefix) \
            and not op_name[len(prefix):].startswith("vmap("):
        return "update"
    return "fwd_bwd"


def hlo_layers(text: str, step_name: str) -> dict:
    """instruction name -> (layer, op_name) for the compiled step."""
    return {n: (layer_of(op, name, step_name), name)
            for n, (op, name) in parse_hlo(text).items()}


# ---------------------------------------------------------------------------
# intervals
# ---------------------------------------------------------------------------

def union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(ivs) -> float:
    return sum(e - s for s, e in ivs)


def intersect(a, b):
    """Intersection of two sorted, disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append([s, e])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def clip(ivs, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in ivs if e > lo and s < hi]


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------

def _self_times(ops):
    """Ops of one device line, (start, end, layer, name, op_name), with two
    more fields: the op's own time and whether it is a leaf. A loop or call
    op spans the ops of its body, so its own time is its span less its
    children's."""
    ops = sorted(ops, key=lambda o: (o[0], -o[1]))
    child = [0.0] * len(ops)
    leaf = [True] * len(ops)
    stack = []
    for i, (s, t, *_) in enumerate(ops):
        while stack and ops[stack[-1]][1] <= s:
            stack.pop()
        if stack and t <= ops[stack[-1]][1]:
            child[stack[-1]] += t - s
            leaf[stack[-1]] = False
        stack.append(i)
    return [(*op, max(op[1] - op[0] - child[i], 0.0), leaf[i])
            for i, op in enumerate(ops)]


def _instr_name(event_name: str) -> str:
    return event_name.split(" = ", 1)[0].lstrip("%").strip()


def reduce(planes, layers: dict, step_name: str) -> dict:
    """``planes``: the trace's planes (``ProfileData.planes``). Times in
    the result are seconds. Raises if the trace holds no device op."""
    planes = list(planes)
    host = defaultdict(list)
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in HOST_SPANS or ev.name == STEP_SPAN:
                    host[ev.name].append([ev.start_ns, ev.start_ns
                                          + ev.duration_ns])
    steps = sorted(host[STEP_SPAN])
    if not steps:
        raise ValueError("the trace holds no bench.step span")
    lo, hi = steps[0][0], steps[-1][1]
    window = (hi - lo) * 1e-9

    devices = []
    op_time = defaultdict(float)
    gaps = []
    for plane in planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = {line.name: list(line.events) for line in plane.lines}
        modules = union([e.start_ns, e.start_ns + e.duration_ns]
                        for e in lines.get("XLA Modules", [])
                        if e.name.startswith(f"jit_{step_name}("))
        ops = []
        for e in lines.get("XLA Ops", []):
            name = _instr_name(e.name)
            s, t = e.start_ns, e.start_ns + e.duration_ns
            if t <= lo or s >= hi:
                continue
            in_step = bool(intersect(modules, [[s, t]]))
            layer, op_name = layers.get(name, ("fwd_bwd", None)) \
                if in_step else ("other", None)
            ops.append((s, t, layer, name, op_name))
        if not ops:
            continue
        ops = _self_times(ops)
        coll = [[o[0], o[1]] for o in ops if o[2] == "exchange"]
        for e in lines.get("Async XLA Ops", []):
            layer = layers.get(_instr_name(e.name), ("", None))[0]
            if layer == "exchange":
                coll.append([e.start_ns, e.start_ns + e.duration_ns])
        busy = clip(union([s, t] for s, t, *_ in ops), lo, hi)
        compute = clip(union([o[0], o[1]] for o in ops
                             if o[6] and o[2] != "exchange"), lo, hi)
        coll = clip(union(coll), lo, hi)
        per_layer = defaultdict(float)
        for s, t, layer, name, op_name, own, _ in ops:
            # an op cut by the window's edge counts in proportion
            d = own * (min(t, hi) - max(s, lo)) / (t - s) * 1e-9 \
                if t > s else 0.0
            per_layer[layer] += d
            op_time[(layer, name, op_name)] += d
        exposed = length(coll) - length(intersect(coll, compute))
        devices.append({
            "name": plane.name, "busy_s": length(busy) * 1e-9,
            "layer_s": dict(per_layer),
            "exchange_s": length(coll) * 1e-9,
            "exchange_exposed_s": exposed * 1e-9,
        })
        # idle gaps inside the window, each labelled by the host span that
        # overlaps it most
        edges = [[lo, lo]] + busy + [[hi, hi]]
        for (_, a), (b, _) in zip(edges, edges[1:]):
            if b > a:
                label = max(HOST_SPANS, key=lambda k: length(
                    intersect(union(host[k]), [[a, b]])))
                if not length(intersect(union(host[label]), [[a, b]])):
                    label = "no span"
                gaps.append([f"{plane.name} {label}", (b - a) * 1e-9])
    if not devices:
        raise ValueError("the trace holds no device op in the window")
    n = len(devices)
    mean = lambda key: sum(d[key] for d in devices) / n
    layer_s = defaultdict(float)
    for d in devices:
        for k, v in d["layer_s"].items():
            layer_s[k] += v / n
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    host_s = {k: sum(e - s for s, e in host[k]) * 1e-9 for k in HOST_SPANS}
    return {
        "steps": len(steps), "window_s": window, "n_devices": n,
        "busy_s": mean("busy_s"), "layer_s": dict(layer_s),
        "exchange_s": mean("exchange_s"),
        "exchange_exposed_s": mean("exchange_exposed_s"),
        "host_s": host_s, "devices": devices,
        "breakdown": {
            "device_ops": [[_label(*k), v / n] for k, v in top],
            "idle_gaps": sorted(gaps, key=lambda g: -g[1])[:10],
        },
    }


def _label(layer, name, op_name):
    tail = "/".join((op_name or "").split("/")[-2:])
    return f"{layer} {name} {tail}".strip()
