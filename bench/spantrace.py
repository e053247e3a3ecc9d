"""The program's own instrumentation in a profiler trace, beyond what
:mod:`bench.tracereduce` keeps.

A :class:`~bench.tracereduce.TraceView` keeps each host event by name and
time, which is all the per-layer readers of the program's spans need
(``prepare_s``, ``transfer_s``, ``aggregate_s``,
``idle_unattributed_share``).  Two things of the program's
instrumentation (``repro.core.tracing``) it drops: the arguments of the
``aires.*`` host spans, where the program's counters ride, and each
device operation's name-scope path, which the profiler stores as the
``tf_op`` stat of the operation's event metadata.  :func:`load` reads
both from the same ``.xplane.pb``; ``jax.profiler.ProfileData`` does not
expose metadata stats, so :func:`op_scopes` decodes them from the
serialized ``XSpace`` itself.  :data:`READS` are the reads that need
them.
"""

from __future__ import annotations

import gzip
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

from bench import tracereduce

#: the prefix of the program's own span and scope names
SPAN_PREFIX = "aires."
#: the stat that holds an operation's name-scope path
SCOPE_STAT = "tf_op"

#: one program span: name, start, end and its arguments
Span = Tuple[str, float, float, dict]
#: one device operation: its name-scope path, start and end
Scoped = Tuple[str, float, float]


@dataclass
class SpanView:
    """A trace view with the program's spans and its operations' scopes."""

    view: tracereduce.TraceView
    #: the program's ``aires.*`` host spans, with their arguments
    spans: List[Span] = field(default_factory=list)
    #: per chip of ``view.chips``, its leaf operations by scope path
    scopes: List[List[Scoped]] = field(default_factory=list)

    def named(self, name: str) -> List[Span]:
        """The program spans called ``name``."""
        return [sp for sp in self.spans if sp[0] == name]

    def scope_share(self, scope: str) -> Optional[float]:
        """Device time under the name scope ``scope`` over the time the
        sweep program ran, in percent, mean over the chips that ran any;
        None if no chip did."""
        shares = []
        for chip, ops in zip(self.view.chips, self.scopes):
            prog = chip.program()
            t = sum(e - s for path, s, e in ops
                    if scope in path.split("/"))
            if prog and t:
                shares.append(t / (prog[2] - prog[1]))
        if not shares:
            return None
        return 100.0 * sum(shares) / len(shares)


def steps_run(sv: SpanView) -> Optional[int]:
    """The steps the sweep program ran, as its counter reports them on
    ``aires.transfer``: the largest over the study's calls (and, on a
    sharded run, over the shards)."""
    runs = [a["steps_run"] for _, _, _, a in sv.named("aires.transfer")
            if "steps_run" in a]
    return max(runs) if runs else None


def active_row_share(sv: SpanView) -> Optional[float]:
    """Of the rows the chunk loop carried through its full chunks, the
    share still running as each chunk started, in percent:
    ``active_row_chunks`` over ``chunks_run`` times ``real_rows``, summed
    over the study's ``aires.transfer`` spans.  The remainder chunk is
    counted in neither."""
    keys = ("active_row_chunks", "chunks_run", "real_rows")
    calls = [a for _, _, _, a in sv.named("aires.transfer")
             if all(k in a for k in keys)]
    rows = sum(a["chunks_run"] * a["real_rows"] for a in calls)
    if not rows:
        return None
    return 100.0 * sum(a["active_row_chunks"] for a in calls) / rows


def _scope_read(scope: str) -> Callable[[SpanView], Optional[float]]:
    return lambda sv: sv.scope_share(scope)


#: what the program's counters and scopes read as, by the name each read
#: would take as a per-layer metric
READS: Dict[str, Callable[[SpanView], Optional[float]]] = {
    "draw_share": _scope_read("aires.draw"),
    "crn_tile_share": _scope_read("aires.crn_tile"),
    "hist_update_share": _scope_read("aires.hist"),
    "ring_update_share": _scope_read("aires.ring"),
    "repair_lane_share": _scope_read("aires.repair_lane"),
    "steps_run": steps_run,
    "active_row_share": active_row_share,
}


# -- the serialized XSpace, read for what ProfileData leaves out ---------
# Field numbers of TSL's public tsl/profiler/protobuf/xplane.proto.
_XSPACE_PLANES = 1
_XPLANE_NAME, _XPLANE_EVENT_METADATA, _XPLANE_STAT_METADATA = 2, 4, 5
_MAP_VALUE = 2
_XEVENTMETADATA_NAME, _XEVENTMETADATA_STATS = 2, 5
_XSTATMETADATA_ID, _XSTATMETADATA_NAME = 1, 2
_XSTAT_METADATA_ID, _XSTAT_STR_VALUE, _XSTAT_REF_VALUE = 1, 5, 7


def _varint(buf, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf, lo: int = 0, hi: Optional[int] = None):
    """(field number, value) of each field of the message in
    ``buf[lo:hi]``; a length-delimited value comes as its (start, end)
    offsets into ``buf``, a varint as its number; fixed-width values are
    skipped."""
    i, hi = lo, len(buf) if hi is None else hi
    while i < hi:
        tag, i = _varint(buf, i)
        number, wire = tag >> 3, tag & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield number, value
        elif wire == 2:
            n, i = _varint(buf, i)
            yield number, (i, i + n)
            i += n
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")


def _text(buf, where: Tuple[int, int]) -> str:
    return bytes(buf[where[0]:where[1]]).decode("utf-8", "replace")


def _map_value(buf, entry: Tuple[int, int]) -> Optional[Tuple[int, int]]:
    for number, value in _fields(buf, *entry):
        if number == _MAP_VALUE:
            return value
    return None


def _scope_stat(buf, stat: Tuple[int, int], scope_id: int,
                stat_names: Dict[int, str]) -> Optional[str]:
    """The value of an XStat if it is the scope stat; a string stat may
    be stored in place or as a reference to a stat metadata's name."""
    mid, value = None, None
    for fn, v in _fields(buf, *stat):
        if fn == _XSTAT_METADATA_ID:
            mid = v
        elif fn == _XSTAT_STR_VALUE:
            value = _text(buf, v)
        elif fn == _XSTAT_REF_VALUE:
            value = stat_names.get(v)
    return value if mid == scope_id else None


def op_scopes(serialized: bytes,
              prefix: str = "/device:") -> Dict[str, Dict[str, str]]:
    """For each plane whose name starts with ``prefix``: the name-scope
    path (the ``tf_op`` stat) of each event metadata that has one, keyed
    by the metadata's name, which is the full name its events carry."""
    buf = memoryview(serialized)
    out: Dict[str, Dict[str, str]] = {}
    for number, plane in _fields(buf):
        if number != _XSPACE_PLANES:
            continue
        name, event_md, stat_md = "", [], []
        for fn, value in _fields(buf, *plane):
            if fn == _XPLANE_NAME:
                name = _text(buf, value)
            elif fn == _XPLANE_EVENT_METADATA:
                event_md.append(_map_value(buf, value))
            elif fn == _XPLANE_STAT_METADATA:
                stat_md.append(_map_value(buf, value))
        if not name.startswith(prefix):
            continue
        stat_names = {}
        for md in filter(None, stat_md):
            fields = dict(_fields(buf, *md))
            stat_names[fields.get(_XSTATMETADATA_ID)] = _text(
                buf, fields.get(_XSTATMETADATA_NAME, (0, 0)))
        scope_id = next((k for k, v in stat_names.items()
                         if v == SCOPE_STAT), None)
        paths: Dict[str, str] = {}
        for md in filter(None, event_md if scope_id is not None else []):
            ev_name, path = None, None
            for fn, value in _fields(buf, *md):
                if fn == _XEVENTMETADATA_NAME:
                    ev_name = _text(buf, value)
                elif fn == _XEVENTMETADATA_STATS:
                    path = _scope_stat(buf, value, scope_id,
                                       stat_names) or path
            if ev_name is not None and path is not None:
                paths[ev_name] = path
        out[name] = paths
    return out


def load(path, span_name: str, chips: int) -> SpanView:
    """:func:`bench.tracereduce.load` of one trace file, with the
    program's spans and the scope path of each leaf operation (empty
    where the trace has none), clipped to the same study span."""
    from jax.profiler import ProfileData

    view = tracereduce.load(path, span_name, chips)
    opener = gzip.open if str(path).endswith(".gz") else open
    with opener(path, "rb") as f:
        serialized = f.read()
    data = ProfileData.from_serialized_xspace(serialized)
    paths = op_scopes(serialized, "/device:TPU:")
    lo, hi = view.span
    spans, devices = [], {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(ev.name, max(ev.start_ns, lo),
                           min(ev.end_ns, hi), dict(ev.stats))
                          for ev in line.events
                          if ev.name.startswith(SPAN_PREFIX)
                          and ev.end_ns > lo and ev.start_ns < hi]
        elif plane.name.startswith("/device:TPU:"):
            idx = int(plane.name.rsplit(":", 1)[1])
            ops = devices.setdefault(idx, [])
            named = paths.get(plane.name, {})
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops += [(named.get(n, ""), max(s, lo), min(e, hi))
                            for n, s, e in tracereduce.leaves(
                                [(ev.name, ev.start_ns, ev.end_ns)
                                 for ev in line.events])
                            if e > lo and s < hi]
    scopes = [devices[i] for i in sorted(devices)[:chips]]
    return SpanView(view, spans, scopes)
