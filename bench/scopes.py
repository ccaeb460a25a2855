"""The program's own names in a profiler trace of the timed window: the
named scopes of the compiled step and the ``repro.*`` host spans.

``reduce`` returns ``tracing.reduce``'s result, every key and label as it
is, with these added:

- ``scope_s``: per scope of ``SCOPES``, the device self time of the step's
  ops named under it, mean over devices (seconds, like ``layer_s``). An op
  belongs to a scope when the scope is a whole segment of its HLO op_name's
  path, also inside a transform's parentheses (``vmap(jvp(attention))``);
  a fusion has its root op's name, as ``tracing.parse_hlo`` resolves it.
- ``program_host_s`` and ``program_host_n``: per ``repro.*`` host span
  name, on any thread, the summed time inside the window and the count of
  spans that overlap it.
- ``idle_producing_s``: per device, the window less the union of its ops,
  intersected with the union of the ``repro.input.produce`` spans; mean
  over devices.

A trace of a program without these scopes and spans gives empty dicts and
0, and the readers in ``metrics/`` then return ``None``.
"""
from __future__ import annotations

import re
from collections import defaultdict

from bench import tracing

SCOPES = ("attention", "mlp", "loss", "elastic.pack", "elastic.exchange",
          "elastic.update", "elastic.unpack")
PROGRAM_SPAN = "repro."
PRODUCE_SPAN = "repro.input.produce"

_SCOPE = {s: re.compile(r"(?:^|[/(])" + re.escape(s) + r"(?:$|[/)])")
          for s in SCOPES}


def scope_of(op_name: str | None) -> str | None:
    """The innermost scope of ``SCOPES`` on ``op_name``'s path, or None."""
    if not op_name:
        return None
    best, at = None, -1
    for s, pat in _SCOPE.items():
        for m in pat.finditer(op_name):
            if m.start() > at:
                best, at = s, m.start()
    return best


def hlo_scopes(text: str) -> dict:
    """instruction name -> scope (or None) for the compiled step."""
    return {n: scope_of(name)
            for n, (_, name) in tracing.parse_hlo(text).items()}


def _window(planes):
    steps = sorted([e.start_ns, e.start_ns + e.duration_ns]
                   for p in planes if p.name.startswith("/host:")
                   for line in p.lines for e in line.events
                   if e.name == tracing.STEP_SPAN)
    return steps[0][0], steps[-1][1]


def program_spans(planes, scopes: dict, step_name: str) -> dict:
    """The added keys of ``reduce``, from the trace's planes and
    ``hlo_scopes`` of the compiled step."""
    planes = list(planes)
    lo, hi = _window(planes)
    host = defaultdict(list)
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PROGRAM_SPAN):
                    host[e.name].append([e.start_ns,
                                         e.start_ns + e.duration_ns])
    host_s = {k: sum(e - s for s, e in tracing.clip(v, lo, hi)) * 1e-9
              for k, v in host.items()}
    host_n = {k: len(tracing.clip(v, lo, hi)) for k, v in host.items()}
    produce = tracing.clip(tracing.union(host.get(PRODUCE_SPAN, [])),
                           lo, hi)

    scope_s, idle_producing, n = defaultdict(float), 0.0, 0
    for plane in planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = {line.name: list(line.events) for line in plane.lines}
        modules = tracing.union(
            [e.start_ns, e.start_ns + e.duration_ns]
            for e in lines.get("XLA Modules", [])
            if e.name.startswith(f"jit_{step_name}("))
        ops = []
        for e in lines.get("XLA Ops", []):
            s, t = e.start_ns, e.start_ns + e.duration_ns
            if t <= lo or s >= hi:
                continue
            name = tracing._instr_name(e.name)
            in_step = bool(tracing.intersect(modules, [[s, t]]))
            ops.append((s, t, scopes.get(name) if in_step else None, name,
                        None))
        if not ops:
            continue
        n += 1
        for s, t, scope, _, _, own, _ in tracing._self_times(ops):
            if scope and t > s:
                # an op cut by the window's edge counts in proportion, as
                # in tracing.reduce
                scope_s[scope] += own * (min(t, hi) - max(s, lo)) \
                    / (t - s) * 1e-9
        busy = tracing.clip(tracing.union([s, t] for s, t, *_ in ops),
                            lo, hi)
        idle = _complement(busy, lo, hi)
        idle_producing += tracing.length(tracing.intersect(idle, produce))
    n = max(n, 1)
    return {"scope_s": {k: v / n for k, v in scope_s.items()},
            "program_host_s": host_s, "program_host_n": host_n,
            "idle_producing_s": idle_producing * 1e-9 / n}


def _complement(ivs, lo, hi):
    """[lo, hi] less the sorted, disjoint intervals ``ivs``."""
    out, at = [], lo
    for s, e in ivs:
        if s > at:
            out.append([at, s])
        at = max(at, e)
    if hi > at:
        out.append([at, hi])
    return out


def reduce(planes, hlo_text: str, step_name: str) -> dict:
    """``tracing.reduce`` of the trace with the keys above added."""
    planes = list(planes)
    out = tracing.reduce(planes, tracing.hlo_layers(hlo_text, step_name),
                         step_name)
    out.update(program_spans(planes, hlo_scopes(hlo_text), step_name))
    return out


def mixed_fusions(hlo_text: str, step_name: str) -> tuple:
    """(fusions of the update layer, those of them whose body holds ops of
    two or more ``elastic.*`` scopes)."""
    comps, calls, current = defaultdict(list), {}, None
    for line in hlo_text.splitlines():
        m = tracing._INSTR.match(line)
        if m and current is not None:
            comps[current].append(m.group(1))
            calls[m.group(1)] = tracing._CALLS.findall(m.group(2))
            continue
        m = tracing._COMP.match(line)
        if m and "=" not in line.split("{")[0].split("(")[0]:
            current = m.group(1)
    ops = tracing.parse_hlo(hlo_text)

    def scopes_in(comp, seen):
        for instr in comps.get(comp, []):
            yield scope_of(ops[instr][1])
            for c in calls[instr]:
                if c not in seen:
                    seen.add(c)
                    yield from scopes_in(c, seen)

    total = mixed = 0
    for name, (layer, _) in tracing.hlo_layers(hlo_text, step_name).items():
        if layer != "update" or ops[name][0] != "fusion":
            continue
        inner = {s for c in calls[name] for s in scopes_in(c, {c})
                 if s and s.startswith("elastic.")}
        total += 1
        mixed += len(inner) > 1
    return total, mixed
