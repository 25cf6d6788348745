"""The device operations of a profiler trace (``.xplane.pb``) with their
``tf_op`` path: the name-scope path of the jitted program's op, which the
profile keeps in each op's event metadata and ``jax.profiler.ProfileData``
does not expose.

A walk over the XSpace protobuf's wire format, with the field numbers of
``tsl/profiler/protobuf/xplane.proto``; no package beyond the standard
library. Only the device planes are decoded.
"""
from __future__ import annotations

from pathlib import Path

OPS_LINE = "XLA Ops"
TF_OP = "tf_op"

# field numbers (xplane.proto)
SPACE_PLANES = 1
PLANE_NAME, PLANE_LINES, PLANE_EVENT_META, PLANE_STAT_META = 2, 3, 4, 5
LINE_NAME, LINE_TIMESTAMP_NS, LINE_EVENTS = 2, 3, 4
EVENT_META_ID, EVENT_OFFSET_PS, EVENT_DURATION_PS = 1, 2, 3
EVENT_META_STATS = 5
STAT_META_NAME = 2
STAT_META_ID, STAT_STR = 1, 5
MAP_KEY, MAP_VALUE = 1, 2


def _varint(buf, i: int) -> tuple:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf):
    """(field number, value) of one message's fields in order: an int for
    a varint or a fixed-width field, a memoryview for a length-delimited
    one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            width = 8 if wire == 1 else 4
            v, i = int.from_bytes(buf[i:i + width], "little"), i + width
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield num, v


def _map(entries) -> dict:
    out = {}
    for entry in entries:
        kv = dict(fields(entry))
        out[kv.get(MAP_KEY, 0)] = kv.get(MAP_VALUE, b"")
    return out


def _tf_op(event_meta, tf_op_id) -> str | None:
    for n, stat in fields(event_meta):
        if n == EVENT_META_STATS:
            f = dict(fields(stat))
            if f.get(STAT_META_ID) == tf_op_id and STAT_STR in f:
                return bytes(f[STAT_STR]).decode()
    return None


def _plane_ops(plane: dict) -> list | None:
    stat_names = {k: bytes(dict(fields(v)).get(STAT_META_NAME, b"")).decode()
                  for k, v in _map(plane[PLANE_STAT_META]).items()}
    tf_op_id = next((k for k, v in stat_names.items() if v == TF_OP), None)
    paths = {k: _tf_op(v, tf_op_id)
             for k, v in _map(plane[PLANE_EVENT_META]).items()}
    for line in plane[PLANE_LINES]:
        f = list(fields(line))
        if bytes(dict(f).get(LINE_NAME, b"")).decode() != OPS_LINE:
            continue
        t0 = dict(f).get(LINE_TIMESTAMP_NS, 0)
        ops = []
        for n, ev in f:
            if n == LINE_EVENTS:
                e = dict(fields(ev))
                start = int(t0 + e.get(EVENT_OFFSET_PS, 0) / 1000)
                end = start + int(e.get(EVENT_DURATION_PS, 0) / 1000)
                ops.append((start, end, paths.get(e.get(EVENT_META_ID))))
        return ops
    return None


def device_ops(path) -> list:
    """Per device (the ``/device:`` planes with an ``XLA Ops`` line, in the
    file's order, as ``bench.tracing.load`` takes them): the ops as
    [(start_ns, end_ns, tf_op path or None)]."""
    buf = memoryview(Path(path).read_bytes())
    out = []
    for num, raw in fields(buf):
        if num != SPACE_PLANES:
            continue
        plane = {PLANE_NAME: b"", PLANE_LINES: [], PLANE_EVENT_META: [],
                 PLANE_STAT_META: []}
        for n, v in fields(raw):
            if n == PLANE_NAME:
                plane[n] = v
            elif n in plane:
                plane[n].append(v)
        if not bytes(plane[PLANE_NAME]).decode().startswith("/device:"):
            continue
        ops = _plane_ops(plane)
        if ops is not None:
            out.append(ops)
    return out


def has_scope(tf_op: str | None, scope: str) -> bool:
    """Is ``scope`` a component of the op's name-scope path (``tf_op`` is
    ``<path>:<type>``, e.g. ``jit(_apply_impl)/jit(numeric_reuse)/gather:``)?"""
    if not tf_op:
        return False
    return scope in tf_op.rsplit(":", 1)[0].split("/")
