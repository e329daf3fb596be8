"""Read a profiler's ``.xplane.pb`` with the installed ``protobuf`` alone.

The file is a serialized ``XSpace`` (``tsl/profiler/protobuf/xplane.proto``).
This module declares the part of that schema the harness reads, with the
same field numbers, and lets ``protobuf`` parse the file against it; fields
it does not declare are skipped.  It imports nothing of TensorFlow or of
the profiler plugins, so loading it costs nothing in set-up.

An event's time comes from its line: ``start = timestamp_ns + offset_ps /
1000``.  An event's ``XEventMetadata`` holds its name (on a TPU's ``XLA
Ops`` line, the HLO text of the instruction) and stats that are the same
for every run of it, among them ``tf_op``: the name scope stack the
instruction was traced under.
"""
from __future__ import annotations

import functools

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

_F = descriptor_pb2.FieldDescriptorProto
_PACKAGE = "bench_xplane"

# message -> [(field, number, type, message type or None, repeated)]
_SCHEMA = {
    "XSpace": [("planes", 1, _F.TYPE_MESSAGE, "XPlane", True)],
    "XPlane": [("name", 2, _F.TYPE_STRING, None, False),
               ("lines", 3, _F.TYPE_MESSAGE, "XLine", True),
               ("event_metadata", 4, _F.TYPE_MESSAGE,
                "XPlane.EventMetadataEntry", True),
               ("stat_metadata", 5, _F.TYPE_MESSAGE,
                "XPlane.StatMetadataEntry", True),
               ("stats", 6, _F.TYPE_MESSAGE, "XStat", True)],
    "XLine": [("name", 2, _F.TYPE_STRING, None, False),
              ("timestamp_ns", 3, _F.TYPE_INT64, None, False),
              ("events", 4, _F.TYPE_MESSAGE, "XEvent", True)],
    "XEvent": [("metadata_id", 1, _F.TYPE_INT64, None, False),
               ("offset_ps", 2, _F.TYPE_INT64, None, False),
               ("duration_ps", 3, _F.TYPE_INT64, None, False)],
    "XStat": [("metadata_id", 1, _F.TYPE_INT64, None, False),
              ("double_value", 2, _F.TYPE_DOUBLE, None, False),
              ("uint64_value", 3, _F.TYPE_UINT64, None, False),
              ("int64_value", 4, _F.TYPE_INT64, None, False),
              ("str_value", 5, _F.TYPE_STRING, None, False),
              ("bytes_value", 6, _F.TYPE_BYTES, None, False),
              ("ref_value", 7, _F.TYPE_UINT64, None, False)],
    "XEventMetadata": [("id", 1, _F.TYPE_INT64, None, False),
                       ("name", 2, _F.TYPE_STRING, None, False),
                       ("stats", 5, _F.TYPE_MESSAGE, "XStat", True)],
    "XStatMetadata": [("id", 1, _F.TYPE_INT64, None, False),
                      ("name", 2, _F.TYPE_STRING, None, False)],
}
# the map fields of XPlane, as protobuf spells a map: a nested entry type
_MAPS = {"EventMetadataEntry": "XEventMetadata",
         "StatMetadataEntry": "XStatMetadata"}


def _fields(msg, fields) -> None:
    for name, number, ftype, type_name, repeated in fields:
        f = msg.field.add(name=name, number=number, type=ftype,
                          label=_F.LABEL_REPEATED if repeated
                          else _F.LABEL_OPTIONAL)
        if type_name:
            f.type_name = f".{_PACKAGE}.{type_name}"


@functools.cache
def _xspace_class():
    fdp = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package=_PACKAGE, syntax="proto3")
    for name, fields in _SCHEMA.items():
        msg = fdp.message_type.add(name=name)
        _fields(msg, fields)
        if name == "XStat":  # XStat's values are one oneof, as upstream
            msg.oneof_decl.add(name="value")
            for f in msg.field[1:]:
                f.oneof_index = 0
        if name == "XPlane":
            for entry, value in _MAPS.items():
                e = msg.nested_type.add(name=entry)
                e.options.map_entry = True
                _fields(e, [("key", 1, _F.TYPE_INT64, None, False),
                            ("value", 2, _F.TYPE_MESSAGE, value, False)])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{_PACKAGE}.XSpace"))


def parse(data: bytes):
    """The ``XSpace`` message of a serialized ``.xplane.pb``."""
    space = _xspace_class()()
    space.ParseFromString(data)
    return space


def stat_value(plane, stat):
    """A stat's value: a number, a string, or for a ``ref_value`` the name
    of the stat metadata it refers to (how repeated strings are stored)."""
    kind = stat.WhichOneof("value")
    if kind is None:
        return None
    if kind == "ref_value":
        return plane.stat_metadata[stat.ref_value].name
    return getattr(stat, kind)


def stats(plane, stat_list) -> dict:
    """{stat name: value} of a list of ``XStat`` of ``plane``."""
    return {plane.stat_metadata[s.metadata_id].name: stat_value(plane, s)
            for s in stat_list}
