"""Op name paths from the metadata a profiler trace keeps.

``jax.profiler.ProfileData`` shows each event's own stats, but not the
stats of its event metadata, nor the trace's ``/host:metadata`` plane.
The ``.xplane.pb`` has both, and they carry an op's name path
(``jit(fwdp)/expand/dedup/sort``, from ``jax.named_scope``):

- on a device plane (the TPU), each "XLA Ops" event's metadata carries a
  ``tf_op`` stat: the path, keyed by the event's name;
- on ``/host:metadata`` (any backend), each program compiled while the
  profiler ran is an event metadata named ``<module>(<program id>)``
  with the optimized program as an ``Hlo Proto`` stat. Each
  instruction's ``metadata.op_name`` is its path; a fusion without one
  takes its fused computation's root's.

The messages are read with classes built here from the fields they need
(field numbers as in XLA's ``xplane.proto`` and ``hlo.proto``), so
nothing beyond ``protobuf`` is imported.
"""

from __future__ import annotations

from functools import lru_cache

HLO_STAT = "Hlo Proto"
PATH_STAT = "tf_op"
METADATA_PLANE = "/host:metadata"


@lru_cache(maxsize=1)
def _classes():
    """(XSpace, HloProto) message classes with just the fields read here;
    every other field is skipped as unknown."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)

    F = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(
        name="gamesman_trace_min.proto", package="tmin", syntax="proto3")
    # message -> [(field, number, type or message name, repeated)]
    schema = {
        "XStat": [("metadata_id", 1, F.TYPE_INT64, False),
                  ("str_value", 5, F.TYPE_STRING, False),
                  ("bytes_value", 6, F.TYPE_BYTES, False),
                  ("ref_value", 7, F.TYPE_UINT64, False)],
        "XEventMetadata": [("name", 2, F.TYPE_STRING, False),
                           ("stats", 5, "XStat", True)],
        "XStatMetadata": [("name", 2, F.TYPE_STRING, False)],
        "EventMetadataEntry": [("key", 1, F.TYPE_INT64, False),
                               ("value", 2, "XEventMetadata", False)],
        "StatMetadataEntry": [("key", 1, F.TYPE_INT64, False),
                              ("value", 2, "XStatMetadata", False)],
        "XPlane": [("name", 2, F.TYPE_STRING, False),
                   ("event_metadata", 4, "EventMetadataEntry", True),
                   ("stat_metadata", 5, "StatMetadataEntry", True)],
        "XSpace": [("planes", 1, "XPlane", True)],
        "OpMetadata": [("op_name", 2, F.TYPE_STRING, False)],
        "HloInstructionProto": [
            ("name", 1, F.TYPE_STRING, False),
            ("metadata", 7, "OpMetadata", False),
            ("id", 35, F.TYPE_INT64, False),
            ("called_computation_ids", 38, F.TYPE_INT64, True)],
        "HloComputationProto": [
            ("instructions", 2, "HloInstructionProto", True),
            ("id", 5, F.TYPE_INT64, False),
            ("root_id", 6, F.TYPE_INT64, False)],
        "HloModuleProto": [("computations", 3, "HloComputationProto",
                            True)],
        "HloProto": [("hlo_module", 1, "HloModuleProto", False)],
    }
    for name, fields in schema.items():
        m = fd.message_type.add(name=name)
        for fname, number, kind, repeated in fields:
            f = m.field.add(name=fname, number=number,
                            label=F.LABEL_REPEATED if repeated
                            else F.LABEL_OPTIONAL)
            if isinstance(kind, str):
                f.type, f.type_name = F.TYPE_MESSAGE, f".tmin.{kind}"
            else:
                f.type = kind
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return tuple(message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"tmin.{n}"))
        for n in ("XSpace", "HloProto"))


def module_paths(hlo_bytes: bytes) -> dict[str, str]:
    """{instruction name: op name path} of one serialized HloProto."""
    _, HloProto = _classes()
    module = HloProto.FromString(hlo_bytes).hlo_module
    comps = {c.id: c for c in module.computations}
    by_id = {i.id: i for c in module.computations for i in c.instructions}

    def path(inst, depth=0) -> str:
        if inst.metadata.op_name or depth > 8:
            return inst.metadata.op_name
        for cid in inst.called_computation_ids:
            comp = comps.get(cid)
            root = by_id.get(comp.root_id) if comp is not None else None
            if root is not None:
                return path(root, depth + 1)
        return ""

    return {i.name: path(i) for i in by_id.values()}


def read(xplane_bytes: bytes) -> dict:
    """The name paths a trace keeps: ``{"events": {device plane: {event
    name: path}}, "programs": {"<module>(<program id>)": {instruction
    name: path}}}``."""
    XSpace, _ = _classes()
    events: dict[str, dict[str, str]] = {}
    programs: dict[str, dict[str, str]] = {}
    for plane in XSpace.FromString(xplane_bytes).planes:
        names = {e.key: e.value.name for e in plane.stat_metadata}
        if plane.name == METADATA_PLANE:
            for entry in plane.event_metadata:
                for stat in entry.value.stats:
                    if names.get(stat.metadata_id) == HLO_STAT \
                            and stat.bytes_value:
                        programs[entry.value.name] = module_paths(
                            stat.bytes_value)
        elif plane.name.startswith("/device:"):
            paths = events.setdefault(plane.name, {})
            for entry in plane.event_metadata:
                for stat in entry.value.stats:
                    if names.get(stat.metadata_id) != PATH_STAT:
                        continue
                    # Repeated strings are stored once, as a reference to
                    # the stat metadata that names them.
                    path = stat.str_value or names.get(stat.ref_value, "")
                    if path:
                        paths[entry.value.name] = path
    return {"events": events, "programs": programs}
