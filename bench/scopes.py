"""Device time by the program's named scopes, and idle gaps by the
engine's own spans: what ``xplane.summarize`` does not read from a trace.

``xplane.summarize`` reduces a profiler trace with the benchmark's
``bench.*`` host spans. This module reads the same ``.xplane.pb`` with
the engine's ``serve.*`` spans too (``serve/engine.py``
``ContinuousBatcher.step``, through ``repro.profile.trace.span``), and
with the map from each compiled program's HLO instructions to their
``op_name`` metadata (``program_op_names``: the profiler's device events
carry the instruction's name, not its metadata). It adds:

  owners    each program class's device self-time by owner: the
            innermost of the program's named scopes
            (``repro.profile.trace.SCOPES``) on the op's ``op_name``;
            else, for a copy XLA inserted to relay out a program
            argument, ``arg <name>`` (``arg caches.k``); else the
            op_name's path below the jitted function
            (``while/body/dynamic_update_slice``: the layer scan's own
            operations); else ``no metadata``;
  idle      idle gaps charged to the innermost span of either family in
            progress at the gap's middle, and each gap longer than
            ``LONG_GAP_S`` with the chain of spans around it;
  steps     each ``serve.step`` span with its children, from which
            ``step_host_ms`` is read.

Window, busy time, program time, kernel time and the op breakdown are
``xplane.summarize``'s, bounded by the ``bench.*`` spans alone.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import json
import re
import statistics

import xplane

SERVE_SPAN = "serve."
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?(%\S+)\s+=\s+(.*)$")
LONG_GAP_S = 0.1
# the fused programs' arguments, whose names an op_name carries when XLA
# copies one into another layout
_ARG_ROOTS = ("params", "tokens", "caches", "positions", "start", "key", "fill_mask")


@dataclasses.dataclass
class Span:
    name: str
    t0: int                # ns, the profiler's clock
    t1: int
    args: dict


@dataclasses.dataclass
class EngineSummary:
    base: xplane.Summary   # exactly what the benchmark reads
    owners: dict           # class -> {owner: device seconds}
    unmapped_s: dict       # class -> seconds of ops absent from the map
    idle: dict             # span name -> idle device seconds (both families)
    long_gaps: list        # [(seconds, [outermost .. innermost span])]
    steps: list            # [(serve.step Span, [child Spans])]

    def program_s(self, cls: str) -> float:
        return sum(self.base.modules.get(cls, []))

    def owner_share(self, cls: str, owner: str) -> float | None:
        """Percent of the class's program time owned by ``owner``."""
        total = self.program_s(cls)
        return 100.0 * self.owners.get(cls, {}).get(owner, 0.0) / total if total else None

    def scoped_share(self, cls: str, scopes) -> float | None:
        total = self.program_s(cls)
        own = self.owners.get(cls, {})
        return 100.0 * sum(own.get(s, 0.0) for s in scopes) / total if total else None


def owner(op_name: str | None) -> str:
    from repro.profile.trace import scope_of

    if op_name is None:
        return "not in map"
    if not op_name:
        return "no metadata"
    s = scope_of(op_name)
    if s is not None:
        return s
    parts = op_name.split("/")
    if len(parts) == 1 and parts[0].split(".")[0].split("[")[0] in _ARG_ROOTS:
        return "arg " + parts[0]
    return "/".join(parts[1:]) or parts[0]


def result_type(text: str) -> str:
    """The result type at the head of an HLO instruction's text after
    ``" = "``: ``bf16[64]{0}``, or a parenthesised tuple."""
    if not text.startswith("("):
        return text.split(" ", 1)[0]
    depth = 0
    for i, ch in enumerate(text):
        depth += (ch == "(") - (ch == ")")
        if depth == 0:
            return text[:i + 1]
    return text


def lookup(names: dict, event: str) -> str | None:
    """op_name of a device op event (``%copy.7 = bf16[2]{0} copy(...)``)
    in one class's map: an entry is the op_name, or, for a name that
    differs between the class's programs (a fill's prompt buckets),
    {result type: op_name}."""
    full, _, rest = event.partition(" = ")
    v = names.get(full.strip())
    return v.get(result_type(rest), "?") if isinstance(v, dict) else v


def _nested_op_names(events, name_of) -> dict:
    """op_name of each op event (name, start, end): its own, or, for an
    op without metadata (XLA built it: the body of a loop XLA made for a
    scatter), that of the innermost op it runs inside."""
    out, stack = {}, []
    for ev in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][0] <= ev[1]:
            stack.pop()
        op = name_of(ev)
        if op == "" and stack and stack[-1][1]:
            op = stack[-1][1]
        out[ev] = op
        stack.append((ev[2], op))
    return out


def _args(event) -> dict:
    out = {}
    for k, v in event.stats:
        if isinstance(v, str) and v.startswith("["):
            try:
                v = json.loads(v)
            except ValueError:
                pass
        out[k] = v
    return out


def read_trace(path: str):
    """(device lines, ``bench.*`` spans, ``serve.*`` Spans) of an
    ``.xplane.pb``; device lines and bench spans as ``xplane.read_events``
    gives them."""
    from jax.profiler import ProfileData

    device, _ = xplane.read_events(path)
    bench, serve = [], []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for e in line.events:
                t0, t1 = e.start_ns, e.start_ns + e.duration_ns
                if e.name.startswith(xplane.HOST_SPAN):
                    bench.append((e.name, t0, t1))
                elif e.name.startswith(SERVE_SPAN):
                    serve.append(Span(e.name, t0, t1, _args(e)))
    return device, bench, serve


def _around(spans, starts, t, back=16):
    """Names of the spans in progress at ``t``, outermost first, from
    the ``back`` latest to start before it (spans sorted by start; the
    innermost span around ``t`` is the latest to start of those that
    have not ended, and an engine step holds fewer than 16 spans)."""
    j = bisect.bisect_right(starts, t)
    inside = [s for s in spans[max(0, j - back):j] if s[2] >= t]
    return [s[0] for s in sorted(inside, key=lambda s: s[1])]


def summarize(device: dict, bench: list, serve: list, op_names: dict) -> EngineSummary:
    """``op_names``: program class -> {full instruction name: op_name or
    {result type: op_name}} (``program_op_names``)."""
    base = xplane.summarize(device, bench)
    if bench:
        w0, w1 = min(s for _, s, _ in bench), max(e for _, _, e in bench)
    else:
        ext = [(s, e) for lines in device.values() for _, s, e in lines.get("XLA Modules", [])]
        w0, w1 = min(s for s, _ in ext), max(e for _, e in ext)
    spans = sorted(list(bench) + [(s.name, s.t0, s.t1) for s in serve], key=lambda h: h[1])
    starts = [h[1] for h in spans]
    owners = collections.defaultdict(lambda: collections.defaultdict(float))
    unmapped = collections.defaultdict(float)
    idle = collections.defaultdict(float)
    long_gaps = []
    for lines in device.values():
        mods = sorted((s, e, xplane._classify(n)) for n, s, e in lines.get("XLA Modules", []))
        mod_starts = [m[0] for m in mods]

        def cls_of(s, e):
            mid = (s + e) / 2
            i = bisect.bisect_right(mod_starts, mid) - 1
            return mods[i][2] if i >= 0 and mods[i][1] >= mid else None

        ops = lines.get("XLA Ops", [])
        named = _nested_op_names(
            ops, lambda ev: lookup(op_names.get(cls_of(ev[1], ev[2]), {}), ev[0]))
        busy_iv = []
        for name, s, e, own in xplane._self_times(ops):
            cs, ce = max(s, w0), min(e, w1)
            if ce <= cs:
                continue
            busy_iv.append((cs, ce))
            cls = cls_of(s, e)
            if not cls:
                continue
            secs = own * (ce - cs) / (e - s) * 1e-9
            op = named[(name, s, e)]
            if op is None:
                unmapped[cls] += secs
            owners[cls][owner(op)] += secs
        merged = xplane._union(busy_iv)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge <= gs:
                continue
            mid = (gs + ge) / 2
            chain = _around(spans, starts, mid)
            idle[chain[-1] if chain else "no span"] += (ge - gs) * 1e-9
            if (ge - gs) * 1e-9 > LONG_GAP_S:
                long_gaps.append(((ge - gs) * 1e-9, _around(spans, starts, mid, len(spans))))
    chips = len(device)
    steps = []
    ordered = sorted(serve, key=lambda s: s.t0)
    for st in (s for s in ordered if s.name == "serve.step"):
        steps.append((st, [c for c in ordered if c is not st
                           and st.t0 <= c.t0 and c.t1 <= st.t1]))
    return EngineSummary(
        base=base,
        owners={c: {k: v / chips for k, v in d.items()} for c, d in owners.items()},
        unmapped_s={c: v / chips for c, v in unmapped.items()},
        idle={k: v / chips for k, v in idle.items()},
        long_gaps=sorted(long_gaps, reverse=True), steps=steps)


def step_host_ms(summary: EngineSummary) -> float | None:
    """Median over decode-only ``serve.step`` spans (no fill span inside)
    of their duration less their ``serve.decode.fetch`` child: the
    engine's host time per decode step, the wait for the device left
    out."""
    out = []
    for st, children in summary.steps:
        names = [c.name for c in children]
        if any(n.startswith("serve.fill.") for n in names) or "serve.decode.fetch" not in names:
            continue
        fetch = sum(c.t1 - c.t0 for c in children if c.name == "serve.decode.fetch")
        out.append((st.t1 - st.t0 - fetch) * 1e-6)
    return statistics.median(out) if out else None


def fill_token_use(before: dict, after: dict) -> float | None:
    """Percent of the fill tokens computed between two ``stats()`` that
    were prompt tokens of the requests the fills admitted."""
    computed = after["fill_tokens_computed"] - before["fill_tokens_computed"]
    prompt = after["fill_tokens_prompt"] - before["fill_tokens_prompt"]
    return 100.0 * prompt / computed if computed else None


def _typed_op_names(hlo_text: str) -> dict:
    """{instruction: (op_name, result type)} of a compiled program."""
    from repro.profile.trace import hlo_op_names

    types = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            types[m.group(1)] = result_type(m.group(2))
    return {k: (v, types.get(k, "")) for k, v in hlo_op_names(hlo_text).items()}


def merge_programs(programs: list) -> dict:
    """One class's map from the maps of its programs (``_typed_op_names``
    each): a name with one op_name in all of them maps to it; one whose
    op_name differs maps to {result type: op_name}, ``"?"`` where two
    programs give one result type two op_names."""
    out = {}
    for name in set().union(*programs):
        seen = [p[name] for p in programs if name in p]
        if len({op for op, _ in seen}) == 1:
            out[name] = seen[0][0]
            continue
        by_type = {}
        for op, typ in seen:
            by_type[typ] = op if by_type.get(typ, op) == op else "?"
        out[name] = by_type
    return out


def program_op_names(batcher, buckets) -> dict:
    """{program class: {instruction: op_name}} of the engine's compiled
    fused programs at the shapes it serves: the decode step, and the fill
    at each prompt bucket (``merge_programs``). Lowered and compiled
    again from the batcher's own jitted functions and arrays, so the
    instruction names are those of the programs that ran (the compile
    cache hands back the same executables)."""
    import jax
    import jax.numpy as jnp

    n = batcher.n_slots
    key = jax.random.fold_in(batcher._key, 0)
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)
    decode = batcher._decode.lower(batcher.params, i32(n, 1), batcher.caches,
                                   i32(n), i32(n), key).compile().as_text()
    fills = [batcher._prefill.lower(batcher.params, batcher.caches, i32(n, s_pad), i32(n),
                                    jnp.zeros((n,), bool), key).compile().as_text()
             for s_pad in buckets]
    return {"decode": merge_programs([_typed_op_names(decode)]),
            "prefill": merge_programs([_typed_op_names(t) for t in fills])}
