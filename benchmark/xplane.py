"""The op metadata of a JAX profiler trace (``*.xplane.pb``), read from
the protobuf wire format in plain Python.

``jax.profiler.ProfileData`` gives each device op's event (its name is
the op's full HLO text) but not the stats of the event's metadata.
Those hold what says where an op came from: ``tf_op``, the JAX name
stack of the op (``jit(_step)/snap_r9/jit(latlng_to_cell_vec)/...``),
and ``hlo_category``, which marks the control-flow containers
(``conditional``, ``while``, ``call``) whose events span the ops they
hold.  Only the metadata maps of the device planes are decoded; their
lines are skipped.

The messages read (``tsl/profiler/protobuf/xplane.proto``): XSpace
``planes`` = 1; XPlane ``name`` = 2, ``event_metadata`` = 4 and
``stat_metadata`` = 5 (maps: key = 1, value = 2); XEventMetadata
``name`` = 2, ``stats`` = 5; XStatMetadata ``id`` = 1, ``name`` = 2;
XStat ``metadata_id`` = 1, ``str_value`` = 5, ``ref_value`` = 7 (the id
of a stat metadata entry whose name is the string).
"""

from __future__ import annotations

from dataclasses import dataclass

DEVICE_PREFIX = "/device:TPU"
WANTED = ("tf_op", "hlo_category")


@dataclass(frozen=True)
class OpMeta:
    tf_op: str          # JAX name stack, "" when the op carries none
    category: str       # hlo_category, "" when absent


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    shift = result = 0
    while True:
        b = buf[i]
        i += 1
        result |= (b & 0x7F) << shift
        if b < 0x80:
            return result, i
        shift += 7


def _fields(buf: bytes, start: int = 0, end: int | None = None):
    """(field number, wire type, value) of one message; a
    length-delimited value is the (start, end) of its bytes."""
    i, end = start, len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            val, i = _varint(buf, i)
        elif wt == 2:
            n, i = _varint(buf, i)
            val = (i, i + n)
            i += n
        elif wt == 1:
            val, i = buf[i:i + 8], i + 8
        elif wt == 5:
            val, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported wire type {wt} at byte {i}")
        yield num, wt, val


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_value(buf: bytes, span):
    for num, wt, val in _fields(buf, *span):
        if num == 2 and wt == 2:
            return val
    return None


def _plane(buf: bytes, span) -> tuple[str, dict[str, OpMeta]]:
    name, stat_names, events = "", {}, []
    for num, wt, val in _fields(buf, *span):
        if wt != 2:
            continue
        if num == 2:
            name = _text(buf, val)
            if not name.startswith(DEVICE_PREFIX):
                return name, {}
        elif num == 5:
            entry = _map_value(buf, val)
            if entry is not None:
                sid, sname = 0, ""
                for n, w, v in _fields(buf, *entry):
                    if n == 1 and w == 0:
                        sid = v
                    elif n == 2 and w == 2:
                        sname = _text(buf, v)
                stat_names[sid] = sname
        elif num == 4:
            entry = _map_value(buf, val)
            if entry is not None:
                events.append(entry)
    ops = {}
    for entry in events:
        ename, stats = "", {}
        for n, w, v in _fields(buf, *entry):
            if n == 2 and w == 2:
                ename = _text(buf, v)
            elif n == 5 and w == 2:
                sid, value = 0, None
                for sn, sw, sv in _fields(buf, *v):
                    if sn == 1 and sw == 0:
                        sid = sv
                    elif sn == 5 and sw == 2:
                        value = _text(buf, sv)
                    elif sn == 7 and sw == 0:
                        value = sv
                stats[sid] = value
        got = {}
        for sid, value in stats.items():
            key = stat_names.get(sid)
            if key in WANTED:
                got[key] = (stat_names.get(value, "")
                            if isinstance(value, int) else value or "")
        ops[ename] = OpMeta(got.get("tf_op", ""), got.get("hlo_category", ""))
    return name, ops


def read(path: str) -> dict[str, dict[str, OpMeta]]:
    """Per device plane of the trace at ``path``: op name (the event
    name ``ProfileData`` reports) -> its OpMeta.  Host planes are left
    out."""
    with open(path, "rb") as fh:
        buf = fh.read()
    out = {}
    for num, wt, val in _fields(buf):
        if num == 1 and wt == 2:
            name, ops = _plane(buf, val)
            if ops:
                out[name] = ops
    return out
