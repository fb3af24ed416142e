"""What ``trace_reduce`` cannot read from the profiler's ``.xplane.pb``: the
scope path of every device operation, and the host plane.

``jax.profiler.ProfileData`` (trace_reduce's reader) gives events their own
stats only.  The scope path JAX gave an HLO operation (``op_name``, e.g.
``jit(_decode_scan_impl)/while/body/closed_call/Linear.layers_3_fc1/
dot_general``) is the stat ``tf_op`` of the event's METADATA, and the
arguments of a ``jax.profiler.TraceAnnotation`` are stats of host events; so
the file is decoded here, with ``google.protobuf`` and a hand-declared
descriptor of the six XSpace messages (TensorFlow's ``xplane_pb2`` is not
installed).

    trace = load(path)          # parsed once per path, whoever asks
    trace.device_ops(0)         # [Op(name, start_ns, dur_ns, scope)]
    trace.programs(0)           # [(program name, start_ns, dur_ns)]
    trace.host_spans()          # [Span(name, start_ns, dur_ns, args)]

All times are nanoseconds on the profiler's one time base (an event's line
timestamp plus its offset), the same numbers ``ProfileData`` gives as
``start_ns``: device operations and host spans can be laid over each other.

The program's vocabulary (flexflow_tpu: ``core/interpreter.py``,
``serve/ops.py``, ``serve/inference_manager.py``, ``obs/trace.py``):
  device  ``<OpClass>.<node name>`` per graph node; inside an attention
          node ``qkv_proj``, ``attend`` (inside it ``kv_write``), ``o_proj``;
          in the step programs ``sample``, ``advance``, ``join``;
  host    tick spans ``serve_step`` / ``decode_stretch`` / ``prefill_stretch``
          (argument ``pc_ns``: ``time.perf_counter_ns()`` at entry), launch
          spans ``*_dispatch`` with ``kind``/``n_steps``/``rows``/
          ``prompt_tokens``/``ctx_sum``, and the other spans of SPANS;
          ``commit`` carries the tokens it appended, by the program that
          made them (TOKENS).

    python -m benchmark.xplane_spans <file.xplane.pb | trace dir>
"""

import bisect
import collections
import functools
import os
import re
import sys

from benchmark.trace_reduce import (CONTAINERS, MODULE_LINE, OP_LINE, _union,
                                    find_xplane, op_name, program_name)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"

Op = collections.namedtuple("Op", "name start_ns dur_ns scope")
Span = collections.namedtuple("Span", "name start_ns dur_ns args")

# a graph node's scope (``Linear.layers_3_fc1``): an operator class of
# flexflow_tpu, a dot, the node's name
NODE = re.compile(r"^[A-Z][A-Za-z0-9]*\.[A-Za-z0-9_.\-]+$")
# scopes the step programs and the attention operator open themselves
STAGES = ("qkv_proj", "kv_write", "attend", "o_proj", "sample", "advance",
          "join")
TICKS = ("serve_step", "decode_stretch", "prefill_stretch")
# every span the serving program opens itself (the rest of the host plane is
# the runtime's: ``PjitFunction``, ``TransferFromDevice``, ...)
SPANS = TICKS + (
    "loop_arrivals", "loop_bookkeep", "loop_idle", "host_admit",
    "host_prepare", "batch_sync", "kv_prepare", "sample_for", "join",
    "readback", "commit",
    "step_dispatch", "decode_scan_dispatch", "prefill_scan_dispatch",
    "join_dispatch", "stage_dispatch", "hop")
# the arguments of ``commit``: tokens appended, by what made them
TOKENS = ("scan_tokens", "join_tokens", "step_tokens", "prefill_tokens")
NO_SCOPE, NO_SPAN = "(no scope)", "(no span)"


@functools.lru_cache(maxsize=1)
def _xspace():
    """The ``XSpace`` message class (tsl/profiler/protobuf/xplane.proto,
    the fields this file reads)."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)

    F = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(
        name="benchmark_xplane.proto", package="benchxplane", syntax="proto3")

    def message(name, *fields):
        m = fd.message_type.add(name=name)
        for fname, number, ftype, *rest in fields:
            f = m.field.add(name=fname, number=number, type=ftype,
                            label=F.LABEL_OPTIONAL)
            for r in rest:
                if r == "repeated":
                    f.label = F.LABEL_REPEATED
                elif r == "oneof":
                    if not m.oneof_decl:
                        m.oneof_decl.add(name="value")
                    f.oneof_index = 0
                else:
                    f.type_name = ".benchxplane." + r
        return m

    def map_entry(parent, name, value):
        e = parent.nested_type.add(name=name)
        e.options.map_entry = True
        e.field.add(name="key", number=1, type=F.TYPE_INT64,
                    label=F.LABEL_OPTIONAL)
        e.field.add(name="value", number=2, type=F.TYPE_MESSAGE,
                    label=F.LABEL_OPTIONAL,
                    type_name=".benchxplane." + value)

    message("XStat", ("metadata_id", 1, F.TYPE_INT64),
            ("double_value", 2, F.TYPE_DOUBLE, "oneof"),
            ("uint64_value", 3, F.TYPE_UINT64, "oneof"),
            ("int64_value", 4, F.TYPE_INT64, "oneof"),
            ("str_value", 5, F.TYPE_STRING, "oneof"),
            ("bytes_value", 6, F.TYPE_BYTES, "oneof"),
            ("ref_value", 7, F.TYPE_UINT64, "oneof"))
    message("XEvent", ("metadata_id", 1, F.TYPE_INT64),
            ("offset_ps", 2, F.TYPE_INT64),
            ("duration_ps", 3, F.TYPE_INT64),
            ("stats", 4, F.TYPE_MESSAGE, "XStat", "repeated"))
    message("XLine", ("id", 1, F.TYPE_INT64), ("name", 2, F.TYPE_STRING),
            ("timestamp_ns", 3, F.TYPE_INT64),
            ("events", 4, F.TYPE_MESSAGE, "XEvent", "repeated"))
    message("XEventMetadata", ("id", 1, F.TYPE_INT64),
            ("name", 2, F.TYPE_STRING),
            ("stats", 5, F.TYPE_MESSAGE, "XStat", "repeated"))
    message("XStatMetadata", ("id", 1, F.TYPE_INT64),
            ("name", 2, F.TYPE_STRING))
    plane = message(
        "XPlane", ("id", 1, F.TYPE_INT64), ("name", 2, F.TYPE_STRING),
        ("lines", 3, F.TYPE_MESSAGE, "XLine", "repeated"),
        ("event_metadata", 4, F.TYPE_MESSAGE,
         "XPlane.EventMetadataEntry", "repeated"),
        ("stat_metadata", 5, F.TYPE_MESSAGE,
         "XPlane.StatMetadataEntry", "repeated"))
    map_entry(plane, "EventMetadataEntry", "XEventMetadata")
    map_entry(plane, "StatMetadataEntry", "XStatMetadata")
    message("XSpace", ("planes", 1, F.TYPE_MESSAGE, "XPlane", "repeated"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("benchxplane.XSpace"))


def _stat_value(stat, stat_names):
    which = stat.WhichOneof("value")
    if which == "ref_value":      # a string kept once, among the stat names
        return stat_names.get(stat.ref_value, "")
    return getattr(stat, which) if which else None


class Trace:
    """One parsed ``.xplane.pb``."""

    def __init__(self, path):
        with open(path, "rb") as f:
            space = _xspace().FromString(f.read())
        self.path = path
        self._planes = {p.name: p for p in space.planes}
        self._cache = {}

    # -- planes -----------------------------------------------------------
    def device_planes(self):
        names = [n for n in self._planes if _DEVICE.match(n)]
        return sorted(names, key=lambda n: int(_DEVICE.match(n).group(1)))

    def _device(self, chip):
        """The plane of chip number ``chip``; None where the trace holds no
        such plane (a CPU run has only the host's)."""
        names = self.device_planes()
        return self._planes[names[chip]] if chip < len(names) else None

    def _events(self, plane, line_name, with_event_stats=False):
        """``(name, start_ns, dur_ns, metadata stats, event stats)`` of the
        lines called ``line_name`` (every line if None)."""
        if plane is None:
            return []
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        meta = {}
        for k, m in plane.event_metadata.items():
            meta[k] = (m.name, {stat_names.get(s.metadata_id):
                                _stat_value(s, stat_names) for s in m.stats})
        out = []
        for line in plane.lines:
            if line_name is not None and line.name != line_name:
                continue
            for e in line.events:
                name, mstats = meta.get(e.metadata_id, ("", {}))
                estats = ({stat_names.get(s.metadata_id):
                           _stat_value(s, stat_names) for s in e.stats}
                          if with_event_stats else None)
                out.append((name, line.timestamp_ns + e.offset_ps / 1e3,
                            e.duration_ps / 1e3, mstats, estats))
        return out

    # -- the device -------------------------------------------------------
    def device_ops(self, chip=0):
        """Every ``XLA Ops`` event of one chip, in start order."""
        key = ("ops", chip)
        if key not in self._cache:
            self._cache[key] = sorted(
                (Op(op_name(n), s, d, (m.get("tf_op") or "").rstrip(":"))
                 for n, s, d, m, _ in self._events(self._device(chip),
                                                   OP_LINE)),
                key=lambda o: o.start_ns)
        return self._cache[key]

    def programs(self, chip=0):
        key = ("programs", chip)
        if key not in self._cache:
            self._cache[key] = sorted(
                ((program_name(n), s, d)
                 for n, s, d, _, _ in self._events(self._device(chip),
                                                   MODULE_LINE)),
                key=lambda p: p[1])
        return self._cache[key]

    # -- the host ---------------------------------------------------------
    def host_spans(self):
        """Every event of the host plane, in start order.  A
        ``TraceAnnotation``'s keyword arguments are the event's stats."""
        if "host" not in self._cache:
            self._cache["host"] = sorted(
                (Span(n, s, d, a) for n, s, d, _, a in self._events(
                    self._planes.get(HOST_PLANE), None, True)),
                key=lambda h: h.start_ns)
        return self._cache["host"]


@functools.lru_cache(maxsize=2)
def load(path):
    return Trace(path)


def for_run(ctx):
    """The trace of the run a reader is called in: ``ctx["xplane"]`` if the
    harness hands it over, else the file ``run.py``'s ``Tracer`` wrote
    under this checkout's ``.bench_trace``."""
    path = ctx.get("xplane") or find_xplane(
        os.path.join(ROOT, ".bench_trace"))
    return load(path)


# -- scopes -------------------------------------------------------------------
def scope_of(op):
    """``(node, stage)`` of a device operation from its scope path: the
    innermost graph-node scope (``Linear.layers_3_fc1``) and the innermost
    stage scope (one of STAGES) below it; each None where the path has
    none."""
    node = stage = None
    for part in op.scope.split("/"):
        if part in STAGES:
            stage = part
        elif NODE.match(part):
            node, stage = part, None
    return node, stage


def label_of(op):
    """What the by-scope table groups by: the stage where there is one,
    else the node's operator class, else NO_SCOPE."""
    node, stage = scope_of(op)
    return stage or (node.split(".", 1)[0] if node else NO_SCOPE)


def has_node_scopes(trace):
    """Whether any device operation of any chip carries a graph-node scope:
    a program compiled before the scopes were there (a stale executable
    from the persistent compile cache, whose key leaves metadata out)
    carries none."""
    if "nodes" not in trace._cache:
        trace._cache["nodes"] = any(
            scope_of(o)[0] for c in range(len(trace.device_planes()))
            for o in trace.device_ops(c))
    return trace._cache["nodes"]


def starting_inside(items, spans, start):
    """The ``items`` whose ``start(item)`` lies inside one of ``spans``
    [(lo, hi)]."""
    spans = sorted(spans)
    los = [lo for lo, _ in spans]
    out = []
    for it in items:
        i = bisect.bisect_right(los, start(it)) - 1
        if i >= 0 and start(it) < spans[i][1]:
            out.append(it)
    return out


def ops_in_programs(trace, programs, chip=0):
    """Device operations (containers left out) that started while one of
    the ``programs`` was executing."""
    spans = [(s, s + d) for n, s, d in trace.programs(chip) if n in programs]
    return starting_inside([o for o in trace.device_ops(chip)
                            if o.name not in CONTAINERS],
                           spans, lambda o: o.start_ns)


def by_scope(trace, chip=0):
    """``({label: seconds}, seconds under a node or stage scope)`` over the
    chip's operations, containers left out."""
    table, named = {}, 0.0
    for o in trace.device_ops(chip):
        if o.name in CONTAINERS:
            continue
        label = label_of(o)
        table[label] = table.get(label, 0.0) + o.dur_ns / 1e9
        if label != NO_SCOPE:
            named += o.dur_ns / 1e9
    return table, named


# -- the host's spans ---------------------------------------------------------
def program_spans(trace):
    """The host spans the serving program opened itself, in start order,
    an outer span before the spans it holds."""
    if "own" not in trace._cache:
        trace._cache["own"] = sorted(
            (h for h in trace.host_spans() if h.name in SPANS),
            key=lambda h: (h.start_ns, -h.dur_ns))
    return trace._cache["own"]


def committed_tokens(trace, kinds=TOKENS):
    """Tokens the ``commit`` spans of the trace appended, of ``kinds``;
    None where the trace holds no ``commit`` span (a program without the
    spans)."""
    commits = [h for h in program_spans(trace) if h.name == "commit"]
    if not commits:
        return None
    return sum(int(h.args.get(k) or 0) for h in commits for k in kinds)


def idle_by_span(trace, chip=0):
    """Device-idle seconds between the first program span's start and the
    last one's end, by the innermost program span that covers them:
    ``{span name or NO_SPAN: seconds}``; None without program spans.  Idle
    is the complement of the union of the chip's ``XLA Ops`` intervals."""
    spans = program_spans(trace)
    if not spans:
        return None
    lo = spans[0].start_ns
    hi = max(h.start_ns + h.dur_ns for h in spans)
    gaps, at = [], lo
    for s, e in _union([(o.start_ns, o.start_ns + o.dur_ns)
                        for o in trace.device_ops(chip)]):
        if e <= lo or s >= hi:
            continue
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if at < hi:
        gaps.append((at, hi))
    # cut the axis at every span edge; a piece belongs to the innermost
    # span open over it (spans of one thread nest, so a stack holds them)
    edges = sorted({x for h in spans
                    for x in (h.start_ns, h.start_ns + h.dur_ns)})
    pieces, stack, k = [], [], 0
    for a, b in zip(edges, edges[1:]):
        while k < len(spans) and spans[k].start_ns <= a:
            stack.append(spans[k])
            k += 1
        while stack and stack[-1].start_ns + stack[-1].dur_ns <= a:
            stack.pop()
        pieces.append((a, b, stack[-1].name if stack else NO_SPAN))
    out, j = {}, 0
    for g0, g1 in gaps:
        while j < len(pieces) and pieces[j][1] <= g0:
            j += 1
        i = j
        while i < len(pieces) and pieces[i][0] < g1:
            a, b, name = pieces[i]
            out[name] = out.get(name, 0.0) + (min(b, g1) - max(a, g0)) / 1e9
            i += 1
    return out


def log_run(trace, ctx):
    """What every traced run logs, once per trace whichever reader asks
    first: the two tables, and the traced span's own token counts and
    seconds (to set against an untraced run of the same seed: what
    tracing costs when it is on)."""
    if trace._cache.get("logged"):
        return
    trace._cache["logged"] = True
    log_tables(trace, ctx["log"])
    at = ctx["clock"].trace_at
    if at is not None:
        a, b = at
        done = b.generated - a.generated + b.prompt_done - a.prompt_done
        ctx["log"](
            f"traced span: {b.t - a.t:.3f}s by the host's stamps, "
            f"{b.generated - a.generated} generated + "
            f"{b.prompt_done - a.prompt_done} prompt tokens done "
            f"({done / (b.t - a.t):.2f} tokens/s), {b.fed - a.fed} prompt "
            f"tokens fed; the commit spans count "
            f"{committed_tokens(trace)} tokens")


def log_tables(trace, log):
    """The by-scope table of the device's time and the by-span table of
    its idle time."""
    table, named = by_scope(trace)
    total = sum(table.values())
    if total:
        log(f"scopes: {100 * named / total:.2f}% of {total:.4f}s of device "
            "operations under a node or stage scope; by scope "
            + str({k: round(v, 4) for k, v in
                   sorted(table.items(), key=lambda x: -x[1])}))
        bare = {}
        for o in trace.device_ops():
            if o.name not in CONTAINERS and label_of(o) == NO_SCOPE:
                key = f"{o.name} <{o.scope.rsplit('/', 1)[-1]}>"
                bare[key] = bare.get(key, 0.0) + o.dur_ns / 1e9
        log("scopes: without one " + str(
            {k: round(v, 5) for k, v in
             sorted(bare.items(), key=lambda x: -x[1])[:8]}))
    idle = idle_by_span(trace)
    if idle:
        total = sum(idle.values())
        leaf = sum(v for k, v in idle.items()
                   if k not in TICKS and k != NO_SPAN)
        log(f"idle: {100 * leaf / total if total else 0:.2f}% of "
            f"{total:.4f}s of device-idle time under a span below the "
            "tick; by innermost span "
            + str({k: round(v, 5) for k, v in
                   sorted(idle.items(), key=lambda x: -x[1])}))


def main(argv):
    path = argv[1]
    if os.path.isdir(path):
        path = find_xplane(path)
    trace = load(path)
    log_tables(trace, print)
    host = {}
    for h in trace.host_spans():
        c, t = host.get(h.name, (0, 0.0))
        host[h.name] = (c + 1, t + h.dur_ns / 1e9)
    print(f"host plane: {len(host)} names")
    for n, (c, t) in sorted(host.items(), key=lambda x: -x[1][1])[:30]:
        print(f"  {t:10.6f}s {c:7d}x  {n[:100]}")
    for h in program_spans(trace)[:40]:
        print(f"  {h.start_ns / 1e6:12.3f}ms {h.dur_ns / 1e6:9.3f}ms "
              f"{h.name} {h.args}")


if __name__ == "__main__":
    main(sys.argv)
