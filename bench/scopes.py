"""Reduction of a profiler trace to the program's own names: device self
time per `jax.named_scope` path, and device idle time per host span.

Device side. Each device operation's event metadata in the raw XSpace
carries `tf_op`, the `jax.named_scope` path it was traced under (say
`jit(train_fn)/while/body/closed_call/level3/histogram/scatter-add:`).
`jax.profiler.ProfileData` does not expose event metadata, so
`metadata_tf_ops` decodes just the device planes' `event_metadata` and
`stat_metadata` maps from the serialized XSpace with a small protobuf
wire-format reader, skipping each plane's (large) `lines`. Events are taken
from `ProfileData` as in `bench/xplane.py` and matched to the metadata by
program and name: a window holds several programs, whose instruction names
may repeat. An operation's program is the `XLA Modules` event it runs
inside (named `<module>(<program id>)`); a metadata entry's is its
`program_id` stat. A scope path is the `tf_op` without JAX's own wrappers (`jit(...)`,
`while`, `body`, ...) and without its last part, the primitive:
`level3/histogram` above.

Time is self time: at each instant of the traced window the innermost
operation running (the one that started last among those open) gets it,
so a `while` and the operations of its body are counted once. An
operation with no scope of its own (the compiler's copies; on the TPU a
`while` carries no `tf_op`) takes the scope of the operation it runs
inside, or, inside none, of the first operation within it that has one.

Host side. Idle time (no operation running on the first device) is put
down, piece by piece, to the innermost `repro.*` span open on the host,
else the innermost harness span (`bench.*`, other than the window), else
`host`.
"""
from __future__ import annotations

import heapq
import re
from bisect import bisect_right

from bench import xplane

PROGRAM_PREFIX = "repro."
HOST = "host"
# Parts of a name-stack path that JAX adds itself, not the program.
WRAPPER = re.compile(
    r"^(\w+\(.*\)|while|body|cond|closed_call|core_call|checkpoint|remat"
    r"|shard_map|custom_jvp_call|custom_vjp_call|branch_\d+_fun|scan)$")

# Protobuf field numbers (tensorflow/tsl/profiler/protobuf/xplane.proto).
SPACE_PLANES = 1
PLANE_NAME, PLANE_EVENT_METADATA, PLANE_STAT_METADATA = 2, 4, 5
MAP_KEY, MAP_VALUE = 1, 2
EVENT_MD_NAME, EVENT_MD_STATS = 2, 5
STAT_MD_NAME = 2
STAT_METADATA_ID, STAT_UINT, STAT_INT, STAT_STR, STAT_REF = 1, 3, 4, 5, 7
MODULES_LINE = "XLA Modules"
PROGRAM_ID = re.compile(r"\((\d+)\)$")


# --- protobuf wire format -------------------------------------------------

def varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf: bytes, start: int = 0, end: int | None = None):
    """(field number, value) of each field of the message buf[start:end]:
    an int for varints, a (start, end) slice for length-delimited fields,
    None for fixed-width ones (which this reader never needs)."""
    i, end = start, len(buf) if end is None else end
    while i < end:
        key, i = varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = varint(buf, i)
        elif wire == 2:
            n, i = varint(buf, i)
            val, i = (i, i + n), i + n
        elif wire == 1:
            val, i = None, i + 8
        elif wire == 5:
            val, i = None, i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, val


def text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_entries(buf: bytes, span):
    """(key, value slice) of one protobuf map entry."""
    key, value = None, None
    for f, v in fields(buf, *span):
        if f == MAP_KEY:
            key = v
        elif f == MAP_VALUE:
            value = v
    return key, value


def metadata_tf_ops(buf: bytes) -> dict[str, tuple[dict, int]]:
    """{device plane name: ({(program id, event metadata name): tf_op},
    conflicts)} from a serialized XSpace: every event metadata of the
    device planes, with "" where it carries no tf_op and a program id of
    None where it carries no `program_id`; the `:<type>` suffix of each
    tf_op is dropped. `conflicts` counts the entries that repeat a
    (program, name) key with another tf_op (the first one is kept)."""
    out = {}
    for f, plane in fields(buf):
        if f != SPACE_PLANES:
            continue
        name, events, stats = None, [], {}
        for pf, v in fields(buf, *plane):
            if pf == PLANE_NAME:
                name = text(buf, v)
            elif pf == PLANE_EVENT_METADATA:
                events.append(v)
            elif pf == PLANE_STAT_METADATA:
                sid, md = _map_entries(buf, v)
                for sf, sv in fields(buf, *md):
                    if sf == STAT_MD_NAME:
                        stats[sid] = text(buf, sv)
        if name is None or not xplane.DEVICE_PLANE.match(name):
            continue
        ids = {v: k for k, v in stats.items()}
        tf_op_id, program_id = ids.get("tf_op"), ids.get("program_id")
        ops, conflicts = {}, 0
        for entry in events:
            _, md = _map_entries(buf, entry)
            ev_name, tf_op, program = None, "", None
            for ef, ev in fields(buf, *md):
                if ef == EVENT_MD_NAME:
                    ev_name = text(buf, ev)
                elif ef == EVENT_MD_STATS:
                    sid, val = _stat(buf, ev, stats)
                    if sid is None:
                        continue
                    if sid == tf_op_id and val:
                        tf_op = val.rsplit(":", 1)[0]
                    elif sid == program_id:
                        program = val
            if ev_name is None:
                continue
            key = (program, ev_name)
            had = ops.get(key)
            if had and tf_op and had != tf_op:
                conflicts += 1
            if not had:
                ops[key] = tf_op
        out[name] = ops, conflicts
    return out


def _stat(buf, span, stat_names):
    """(metadata id, value) of one XStat whose value is a string, a
    referenced string or an integer; (metadata id, None) for another kind."""
    sid, val = None, None
    for f, v in fields(buf, *span):
        if f == STAT_METADATA_ID:
            sid = v
        elif f == STAT_STR:
            val = text(buf, v)
        elif f == STAT_REF:
            val = stat_names.get(v)
        elif f in (STAT_UINT, STAT_INT):
            val = v
    return sid, val


def _program_of(modules, start_ns: float):
    """Program id of the module event (start, end, id) running at
    `start_ns`, from `modules` sorted by start; None inside none."""
    i = bisect_right(modules, (start_ns, float("inf"))) - 1
    if i >= 0 and modules[i][0] <= start_ns < modules[i][1]:
        return modules[i][2]
    return None


def scope_of(tf_op: str | None) -> str:
    """The program's part of a name-stack path: "" where it has none."""
    if not tf_op:
        return ""
    parts = tf_op.split("/")[:-1]
    return "/".join(p for p in parts if p and not WRAPPER.match(p))


# --- the trace --------------------------------------------------------------

def load_bytes(buf: bytes) -> dict:
    """{"devices": [[(scope, start_ns, end_ns), ...] per device plane],
    "spans": [(name, start_ns, end_ns), ...] of host spans named `bench.*`
    or `repro.*`, "unmatched": device events that no metadata of their
    program and name describes, "conflicts": metadata keys with two
    tf_ops}."""
    from jax.profiler import ProfileData

    tf_ops = metadata_tf_ops(buf)
    pd = ProfileData.from_serialized_xspace(buf)
    devices, spans, unmatched, conflicts = [], [], 0, 0
    for plane in pd.planes:
        if xplane.DEVICE_PLANE.match(plane.name):
            names, clashes = tf_ops.get(plane.name, ({}, 0))
            conflicts += clashes
            lines = list(plane.lines)
            modules = sorted(
                (ev.start_ns, ev.end_ns, int(m.group(1)))
                for ln in lines if ln.name == MODULES_LINE
                for ev in ln.events
                if (m := PROGRAM_ID.search(ev.name)))
            ops = [ln for ln in lines if ln.name == xplane.OPS_LINE] or lines
            evs = []
            for ln in ops:
                for ev in ln.events:
                    key = (_program_of(modules, ev.start_ns), ev.name)
                    tf_op = names.get(key)
                    unmatched += tf_op is None
                    evs.append((scope_of(tf_op), ev.start_ns, ev.end_ns))
            devices.append(evs)
        elif plane.name.startswith("/host:"):
            spans += [(ev.name, ev.start_ns, ev.end_ns)
                      for ln in plane.lines for ev in ln.events
                      if ev.name.startswith((xplane.SPAN_PREFIX,
                                             PROGRAM_PREFIX))]
    return {"devices": devices, "spans": spans, "unmatched": unmatched,
            "conflicts": conflicts}


def _resolve(evs):
    """Scope of each of `evs` ((start, -end, scope), sorted): its own; else
    that of the event it starts inside; else, for an event inside no
    other, that of the first event inside it that has one (a `while`
    carries no `tf_op` on the TPU, its body does)."""
    first_inside = [sc for _, _, sc in evs]
    parent, stack = [None] * len(evs), []
    for i, (s, _, sc) in enumerate(evs):
        while stack and -evs[stack[-1]][1] <= s:
            stack.pop()
        parent[i] = stack[-1] if stack else None
        if sc:
            for j in reversed(stack):
                if first_inside[j]:
                    break
                first_inside[j] = sc
        stack.append(i)
    out = []
    for i, (_, _, sc) in enumerate(evs):
        p = parent[i]
        out.append(sc or (out[p] if p is not None else first_inside[i]))
    return out


def self_time(events, w0: float, w1: float):
    """({scope: self ns}, busy ns, idle intervals) of one device's events
    over [w0, w1). At each instant the open event that started last (the
    shorter on a tie) is the innermost and gets the time, under the scope
    `_resolve` gives it."""
    evs = sorted((max(s, w0), -min(e, w1), sc) for sc, s, e in events
                 if e > w0 and s < w1)
    scope = _resolve(evs)
    bounds = sorted({w0, w1, *(s for s, _, _ in evs),
                     *(-e for _, e, _ in evs)})
    # Open events as (-start, end, index): the heap's top is the innermost.
    # Events that ended are dropped when they reach the top.
    open_, out, idle, busy, k = [], {}, [], 0.0, 0
    for b, nxt in zip(bounds, bounds[1:]):
        while k < len(evs) and evs[k][0] <= b:
            s, neg_e, _ = evs[k]
            if -neg_e > b:
                heapq.heappush(open_, (-s, -neg_e, k))
            k += 1
        while open_ and open_[0][1] <= b:
            heapq.heappop(open_)
        if open_:
            sc = scope[open_[0][2]]
            out[sc] = out.get(sc, 0.0) + (nxt - b)
            busy += nxt - b
        else:
            idle.append((b, nxt))
    return out, busy, idle


def _span_segments(spans, w0, w1):
    """Sorted [(start, end, label)] cutting [w0, w1) where the innermost
    open span changes: `repro.*` spans before harness spans, shorter
    before longer."""
    inner = [(n, s, e) for n, s, e in spans
             if n != xplane.WINDOW_SPAN and e > w0 and s < w1]
    cuts = sorted({w0, w1, *(max(s, w0) for _, s, _ in inner),
                   *(min(e, w1) for _, _, e in inner)})
    segs = []
    for a, b in zip(cuts, cuts[1:]):
        t = (a + b) / 2
        best = None
        for n, s, e in inner:
            if s <= t < e:
                rank = (not n.startswith(PROGRAM_PREFIX), e - s)
                if best is None or rank < best[0]:
                    best = (rank, n)
        segs.append((a, b, best[1] if best else HOST))
    return segs


def gaps_by_span(idle, spans, w0, w1) -> dict[str, float]:
    """{span name: idle ns} with each idle interval split at span edges."""
    segs = _span_segments(spans, w0, w1)
    starts = [a for a, _, _ in segs]
    out = {}
    for s, e in idle:
        i = max(bisect_right(starts, s) - 1, 0)
        while i < len(segs) and segs[i][0] < e:
            a, b, name = segs[i]
            got = min(b, e) - max(a, s)
            if got > 0:
                out[name] = out.get(name, 0.0) + got
            i += 1
    return out


def reduce(trace: dict) -> dict | None:
    """window_s, busy_s, self_s ({scope: seconds}, averaged over devices),
    scoped_share (0-1: busy time under a program scope), gap_s ({span:
    idle seconds} on the first device), program_spans (how many `repro.*`
    spans the window holds), unmatched and conflicts (`load_bytes`); None when the trace holds no
    window span or no device plane."""
    windows = [(s, e) for n, s, e in trace["spans"] if n == xplane.WINDOW_SPAN]
    if not windows or not trace["devices"]:
        return None
    w0, w1 = windows[0]
    n_dev = len(trace["devices"])
    self_ns, busy_ns, gaps = {}, 0.0, {}
    for i, events in enumerate(trace["devices"]):
        got, busy, idle = self_time(events, w0, w1)
        for k, v in got.items():
            self_ns[k] = self_ns.get(k, 0.0) + v
        busy_ns += busy
        if i == 0:
            gaps = gaps_by_span(idle, trace["spans"], w0, w1)
    scoped = sum(v for k, v in self_ns.items() if k)
    program_spans = sum(1 for n, s, e in trace["spans"]
                        if n.startswith(PROGRAM_PREFIX) and e > w0 and s < w1)
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_ns / n_dev * 1e-9,
        "self_s": {k: v / n_dev * 1e-9 for k, v in
                   sorted(self_ns.items(), key=lambda kv: -kv[1])},
        "scoped_share": scoped / busy_ns if busy_ns else 0.0,
        "gap_s": {k: v * 1e-9 for k, v in
                  sorted(gaps.items(), key=lambda kv: -kv[1])},
        "program_spans": program_spans,
        "unmatched": trace.get("unmatched", 0),
        "conflicts": trace.get("conflicts", 0),
    }


def under(self_s: dict, *names: str) -> float | None:
    """Seconds of self time whose scope path holds every one of `names`;
    None where no scope path does (a program that names no such scope)."""
    got = [v for k, v in self_s.items()
           if all(n in k.split("/") for n in names)]
    return sum(got) if got else None


def program_gaps(red: dict, *leave_out: str) -> float | None:
    """Idle seconds under `repro.*` spans other than `leave_out`; None where
    no program span was open in the window."""
    if not red["program_spans"]:
        return None
    return sum(v for k, v in red["gap_s"].items()
               if k.startswith(PROGRAM_PREFIX) and k not in leave_out)


def per_unit(ctx: dict, names: tuple, unit: str, scale: float):
    """`scale` × self seconds under the scope path `names`, over
    `ctx["result"][unit]` (rounds, rows); None where either is missing."""
    red, n = from_ctx(ctx), ctx["result"].get(unit)
    got = under(red["self_s"], *names) if red is not None else None
    return None if got is None or not n else scale * got / n


def per_level(self_s: dict, name: str) -> dict[str, float]:
    """{level<d>: seconds} of self time under `name`."""
    out = {}
    for k, v in self_s.items():
        parts = k.split("/")
        if name in parts:
            lvl = next((p for p in parts if re.fullmatch(r"level\d+", p)), "")
            out[lvl] = out.get(lvl, 0.0) + v
    return dict(sorted(out.items()))


def from_ctx(ctx: dict) -> dict | None:
    """The reduction of the traced window handed to the per-layer readers
    (`ctx["xplane"]`, the trace's bytes), made once per run and put into
    the notes with the split per scope, per level and per span; None
    without a trace or a window in it."""
    if "scopes" not in ctx:
        buf = ctx.get("xplane")
        red = reduce(load_bytes(buf)) if buf else None
        ctx["scopes"] = red
        if red is not None:
            ctx["notes"]["scopes"] = dict(
                red, per_level={n: per_level(red["self_s"], n) for n in
                                ("histogram", "split", "repartition")})
    return ctx["scopes"]
