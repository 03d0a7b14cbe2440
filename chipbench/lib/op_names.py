"""The jax-side name of a device op, which ``jax.profiler.ProfileData`` does
not show.

On a v5e trace (looked at by hand, PR 27) a device event's NAME is the HLO
instruction's text without its ``metadata={...}``, and its own stats are
three timing fields.  The ``op_name`` that a ``jax.named_scope`` or a Pallas
``name=`` lands in (``jit(raw)/cache_append/vmap(vmap())/scatter:``,
``jit(raw)/flash_decode/pallas_call:``) is the stat ``tf_op`` of the event's
METADATA entry in the plane's ``event_metadata`` table, which ``ProfileData``
has no accessor for.  So this file reads the ``.xplane.pb`` with protobuf
itself, through a schema declared here that holds only the fields it needs
(field numbers of ``tsl/profiler/protobuf/xplane.proto``; protobuf skips the
rest): per plane the lines' events (metadata id, offset, duration) and the
two metadata tables.
"""
from __future__ import annotations

import functools
import re

TF_OP = "tf_op"
_WRAPPED = re.compile(r"[A-Za-z_]\w*\((.*)\)")


def scopes_of(name):
    """The scopes a jax-side op name was traced under: its path elements
    with the transformations taken off, ``jit(...)`` elements left out.
    ``jit(step)/transpose(jvp(flash_bwd_dq))/pallas_call:`` ->
    {"flash_bwd_dq", "pallas_call"};
    ``jit(raw)/cache_append/vmap(vmap())/scatter:`` ->
    {"cache_append", "", "scatter"}; the function a ``jit`` names is not a
    scope."""
    out = set()
    # a fused op lists the names it was made of: "a/b:;c/d:"
    for element in re.split(r"[/;]", name.replace(":", "")):
        if element.startswith(("jit(", "pjit(")):
            continue
        while True:
            m = _WRAPPED.fullmatch(element)
            if m is None:
                break
            element = m.group(1)
        out.add(element)
    return frozenset(out)


@functools.cache
def _xspace_class():
    """The message class of ``XSpace``, built once from a descriptor that is
    declared here, not generated (no ``xplane_pb2`` ships with jax)."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    F = descriptor_pb2.FieldDescriptorProto
    scalar = {"int64": F.TYPE_INT64, "uint64": F.TYPE_UINT64,
              "string": F.TYPE_STRING}
    fd = descriptor_pb2.FileDescriptorProto(
        name="chipbench_xplane_subset.proto", package="chipbench_xplane",
        syntax="proto3")

    def message(name, *fields):
        m = fd.message_type.add(name=name)
        for fname, number, ftype, repeated in fields:
            f = m.field.add(name=fname, number=number,
                            label=F.LABEL_REPEATED if repeated
                            else F.LABEL_OPTIONAL)
            if ftype in scalar:
                f.type = scalar[ftype]
            else:
                f.type, f.type_name = F.TYPE_MESSAGE, \
                    f".chipbench_xplane.{ftype}"

    message("XStat", ("metadata_id", 1, "int64", False),
            ("str_value", 5, "string", False),
            ("ref_value", 7, "uint64", False))
    message("XEvent", ("metadata_id", 1, "int64", False),
            ("offset_ps", 2, "int64", False),
            ("duration_ps", 3, "int64", False))
    message("XLine", ("name", 2, "string", False),
            ("timestamp_ns", 3, "int64", False),
            ("events", 4, "XEvent", True))
    message("XEventMetadata", ("name", 2, "string", False),
            ("stats", 5, "XStat", True))
    message("XStatMetadata", ("name", 2, "string", False))
    # a proto map is a repeated entry message on the wire
    message("EventMetadataEntry", ("key", 1, "int64", False),
            ("value", 2, "XEventMetadata", False))
    message("StatMetadataEntry", ("key", 1, "int64", False),
            ("value", 2, "XStatMetadata", False))
    message("XPlane", ("name", 2, "string", False),
            ("lines", 3, "XLine", True),
            ("event_metadata", 4, "EventMetadataEntry", True),
            ("stat_metadata", 5, "StatMetadataEntry", True))
    message("XSpace", ("planes", 1, "XPlane", True))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("chipbench_xplane.XSpace"))


def named_events(path, plane_name, line_name):
    """[(tf_op, start_s, end_s)] of one line of one plane, on the trace's
    clock; ``tf_op`` is "" for an event whose metadata carries none.  An
    absent plane or line reads as []."""
    space = _xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    for plane in space.planes:
        if plane.name != plane_name:
            continue
        stat_name = {e.key: e.value.name for e in plane.stat_metadata}
        tf_op = {}
        for entry in plane.event_metadata:
            for st in entry.value.stats:
                if stat_name.get(st.metadata_id) == TF_OP:
                    # a string stat is stored inline or as a reference to
                    # a stat-metadata entry whose name is the string
                    tf_op[entry.key] = st.str_value or \
                        stat_name.get(st.ref_value, "")
        for line in plane.lines:
            if line.name != line_name:
                continue
            t0 = line.timestamp_ns * 1e-9
            return [(tf_op.get(ev.metadata_id, ""),
                     t0 + ev.offset_ps * 1e-12,
                     t0 + (ev.offset_ps + ev.duration_ps) * 1e-12)
                    for ev in line.events]
    return []
