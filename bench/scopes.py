"""The program's own view of a profiler trace: device time by named scope,
program spans, and idle gaps labelled with the span the host was in.

``bench/trace.py`` reduces a trace to busy time, top operations and idle
gaps under the benchmark's ``bench.`` spans; this module adds what the
program itself writes into the trace, and leaves that reduction as it is:

* every device operation's event metadata carries a ``tf_op`` stat, the
  operation's scope path (``jit(_bfs_loop)/while/body/bfs.level/push/...``);
  the program names its phases with ``jax.named_scope`` (the vocabulary
  ``repro.core.telemetry.SCOPES``), so a scope's device time is the self
  time of the operations whose path holds it, clipped to the window and
  divided by the chips used, as ``top_ops`` is;
* ``repro.`` spans are the program's host spans (``telemetry.span``);
* an idle gap is labelled with the innermost span of either prefix,
  ``bench.`` or ``repro.``, that holds its midpoint: ``bench.call`` where
  the host waited in ``block_until_ready``, ``repro.bfs.dispatch`` where it
  was inside the driver, ``repro.gc`` where it was collecting garbage.

``jax.profiler.ProfileData`` does not expose event-metadata stats, so the
``tf_op`` stats are decoded from the ``XSpace`` protobuf here, with a schema
that names only the fields read (``tsl/profiler/protobuf/xplane.proto``).

    python bench/scopes.py TRACE.xplane.pb

prints the reduction of one trace as a JSON line.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
import sys

if __package__ in (None, ""):
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from bench import trace  # noqa: E402

SPAN_PREFIXES = ("bench.", "repro.")
TF_OP = "tf_op"


def _xspace_class():
    """A message class for the parts of ``XSpace`` this module reads."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    field = descriptor_pb2.FieldDescriptorProto
    one, many = field.LABEL_OPTIONAL, field.LABEL_REPEATED
    proto = descriptor_pb2.FileDescriptorProto(
        name="bench_xspace.proto", package="bench_xspace", syntax="proto3")

    def message(name, *fields):
        m = proto.message_type.add(name=name)
        for fname, number, kind, label in fields:
            f = m.field.add(name=fname, number=number, label=label,
                            type=field.TYPE_MESSAGE if kind[0].isupper()
                            else getattr(field, f"TYPE_{kind.upper()}"))
            if kind[0].isupper():
                f.type_name = f".bench_xspace.{kind}"

    message("XStat", ("metadata_id", 1, "int64", one),
            ("str_value", 5, "string", one), ("ref_value", 7, "uint64", one))
    message("XEventMetadata", ("name", 2, "string", one),
            ("stats", 5, "XStat", many))
    message("XStatMetadata", ("name", 2, "string", one))
    # map<int64, M> fields are repeated {key = 1, value = 2} entries
    message("EventEntry", ("key", 1, "int64", one),
            ("value", 2, "XEventMetadata", one))
    message("StatEntry", ("key", 1, "int64", one),
            ("value", 2, "XStatMetadata", one))
    message("XPlane", ("name", 2, "string", one),
            ("event_metadata", 4, "EventEntry", many),
            ("stat_metadata", 5, "StatEntry", many))
    message("XSpace", ("planes", 1, "XPlane", many))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(proto)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xspace.XSpace"))


def scope_paths(data: bytes) -> dict:
    """``{(device plane, event name): tf_op}`` from a serialized XSpace."""
    out = {}
    for plane in _xspace_class().FromString(data).planes:
        if not plane.name.startswith(trace.DEVICE_PREFIX):
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        for entry in plane.event_metadata:
            for stat in entry.value.stats:
                if stat_names.get(stat.metadata_id) != TF_OP:
                    continue
                value = (stat.str_value if not stat.ref_value
                         else stat_names.get(stat.ref_value, ""))
                out.setdefault((plane.name, entry.value.name), value)
    return out


def scope_names(tf_op: str) -> set:
    """The scopes of a ``tf_op`` path: its components but the last (the
    operation itself), each without a ``:type`` suffix."""
    return {part.split(":")[0] for part in tf_op.split("/")[:-1]}


def read_events(path: str):
    """``({chip: [(op, start_ns, end_ns, scopes)]}, [(span, start_ns,
    end_ns)])``: the device operations with their scope names, and the
    spans of both prefixes."""
    from jax.profiler import ProfileData

    data = pathlib.Path(path).read_bytes()
    paths = scope_paths(data)
    ops: dict[str, list] = {}
    spans = []
    for plane in ProfileData.from_serialized_xspace(data).planes:
        on_device = plane.name.startswith(trace.DEVICE_PREFIX)
        for line in plane.lines:
            if on_device and line.name != trace.OPS_LINE:
                continue
            for ev in line.events:
                start = float(ev.start_ns)
                end = start + float(ev.duration_ns)
                if on_device:
                    scopes = scope_names(paths.get((plane.name, ev.name), ""))
                    ops.setdefault(plane.name, []).append(
                        (ev.name, start, end, scopes))
                elif ev.name.startswith(SPAN_PREFIXES):
                    spans.append((ev.name, start, end))
    return ops, spans


@dataclasses.dataclass
class Scoped:
    window_s: float
    busy_s: float
    chips: int
    scope_s: dict        # {scope: seconds} for each name asked for
    scoped_s: float      # self time of operations under any of them
    unscoped_s: float    # self time of operations under none of them
    unscoped_ops: list   # [[op, seconds], ...] of that, longest first
    spans: list          # [[name, start_s, end_s]], from the window's start
    idle_gaps: list      # [[label, seconds], ...], longest first


def reduce_events(ops: dict, spans: list, names, *,
                  top: int = 10) -> Scoped:
    """Scope times, program spans and labelled gaps inside the window."""
    windows = [s for s in spans if s[0] == trace.WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {trace.WINDOW_SPAN!r} span in the trace")
    _, lo, hi = windows[0]
    names = tuple(names)
    scope_ns = dict.fromkeys(names, 0.0)
    unscoped: dict[str, float] = {}
    scoped_ns, busy_ns, busy, chips = 0.0, 0.0, [], 0
    for chip_ops in ops.values():
        inside = [(i, max(a, lo), min(b, hi))
                  for i, (_, a, b, _) in enumerate(chip_ops)
                  if min(b, hi) > max(a, lo)]
        if not inside:
            continue
        chips += 1
        chip_busy = trace.merge((a, b) for _, a, b in inside)
        busy_ns += sum(b - a for a, b in chip_busy)
        busy.extend(chip_busy)
        # events keyed by index: each event's own self time
        for i, t in trace.self_times(inside).items():
            op, _, _, held = chip_ops[i]
            for name in names:
                if name in held:
                    scope_ns[name] += t
            if held.isdisjoint(names):
                key = trace.short_name(op)
                unscoped[key] = unscoped.get(key, 0.0) + t
            else:
                scoped_ns += t
    if not chips:
        raise ValueError("no device operation ran in the window")
    idle = trace.gaps(trace.merge(busy), lo, hi)
    idle.sort(key=lambda g: g[0] - g[1])
    return Scoped(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy_ns * 1e-9 / chips,
        chips=chips,
        scope_s={k: ns * 1e-9 / chips for k, ns in scope_ns.items()},
        scoped_s=scoped_ns * 1e-9 / chips,
        unscoped_s=sum(unscoped.values()) * 1e-9 / chips,
        unscoped_ops=[[k, ns * 1e-9 / chips] for k, ns in sorted(
            unscoped.items(), key=lambda kv: -kv[1])[:top]],
        spans=[[name, (max(a, lo) - lo) * 1e-9, (min(b, hi) - lo) * 1e-9]
               for name, a, b in sorted(spans, key=lambda s: s[1])
               if name.startswith("repro.") and min(b, hi) > max(a, lo)],
        idle_gaps=[[trace.label_at(spans, (a + b) / 2), (b - a) * 1e-9]
                   for a, b in idle[:top]])


def reduce_trace(path: str, names=None, *, top: int = 10) -> Scoped:
    """:func:`reduce_events` of a trace file; ``names`` defaults to the
    program's scope vocabulary."""
    if names is None:
        from repro.core.telemetry import SCOPES as names
    return reduce_events(*read_events(path), names, top=top)


def summary(scoped: Scoped, *, top: int = 10) -> dict:
    """The reduction as a result line's ``breakdown`` would print it."""
    by_span: dict[str, list] = {}
    for name, a, b in scoped.spans:
        by_span.setdefault(name, []).append(b - a)
    return {
        "window_s": scoped.window_s, "busy_s": scoped.busy_s,
        "scopes": sorted(([k, v] for k, v in scoped.scope_s.items() if v),
                         key=lambda kv: -kv[1])[:top],
        "scoped_s": scoped.scoped_s, "unscoped_s": scoped.unscoped_s,
        "unscoped_ops": scoped.unscoped_ops[:5],
        "idle_gaps": scoped.idle_gaps,
        "spans": {k: {"count": len(v), "mean_ms": 1e3 * sum(v) / len(v),
                      "max_ms": 1e3 * max(v)}
                  for k, v in sorted(by_span.items())}}


if __name__ == "__main__":
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent
                           / "src"))
    print(json.dumps(summary(reduce_trace(sys.argv[1]))))
