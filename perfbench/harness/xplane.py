"""The HLO op names of the programs in a profiler trace file, which
``jax.profiler.ProfileData`` does not expose.

The profiler stores each program it saw run as an ``HloProto`` in a stat
of the ``/host:metadata`` plane.  Every instruction there carries the
op-name path that ``jax.named_scope`` writes (``OpMetadata.op_name``); a
fusion carries its root's.  This module decodes just that much of the
``XSpace`` protocol buffer (``tsl/profiler/protobuf/xplane.proto``) and of
``HloProto`` (``xla/service/hlo.proto``), with the wire format alone.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, Iterator, Tuple

METADATA_PLANE = b"/host:metadata"
# field numbers: XSpace.planes; XPlane.name, .event_metadata;
# XEventMetadata.stats; XStat.bytes_value; HloProto.hlo_module;
# HloModuleProto.name, .computations; HloComputationProto.instructions;
# HloInstructionProto.name, .metadata; OpMetadata.op_name
_PLANES, _PLANE_NAME, _EVENT_MD = 1, 2, 4
_MD_STATS, _STAT_BYTES = 5, 6
_HLO_MODULE, _MODULE_NAME, _COMPUTATIONS, _INSTRUCTIONS = 1, 1, 3, 2
_INSTR_NAME, _INSTR_METADATA, _OP_NAME = 1, 7, 2


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: ints for varints, bytes for
    length-delimited fields; fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
            yield num, v
        elif wire == 2:
            ln, i = _varint(buf, i)
            yield num, buf[i:i + ln]
            i += ln
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"unsupported wire type {wire}")


def _first(buf: bytes, num: int, default=b""):
    for n, v in _fields(buf):
        if n == num:
            return v
    return default


def _module_op_names(module: bytes) -> Dict[str, str]:
    out = {}
    for num, comp in _fields(module):
        if num != _COMPUTATIONS:
            continue
        for cnum, instr in _fields(comp):
            if cnum != _INSTRUCTIONS:
                continue
            name = _first(instr, _INSTR_NAME)
            md = _first(instr, _INSTR_METADATA)
            op_name = _first(md, _OP_NAME) if md else b""
            if name and op_name:
                out[name.decode()] = op_name.decode("utf-8", "replace")
    return out


def hlo_op_names(data: bytes) -> Dict[str, Dict[str, str]]:
    """``{module name: {instruction name: op name}}`` of every program in a
    serialized ``XSpace``.  Programs that share a name share one map."""
    out: Dict[str, Dict[str, str]] = {}
    for num, plane in _fields(data):
        if num != _PLANES or _first(plane, _PLANE_NAME) != METADATA_PLANE:
            continue
        for pnum, entry in _fields(plane):
            if pnum != _EVENT_MD:
                continue
            md = _first(entry, 2)                    # the map entry's value
            for snum, stat in _fields(md):
                proto = _first(stat, _STAT_BYTES) if snum == _MD_STATS \
                    else b""
                module = _first(proto, _HLO_MODULE) if proto else b""
                if module:
                    name = _first(module, _MODULE_NAME).decode()
                    out.setdefault(name, {}).update(_module_op_names(module))
    return out


def load_op_names(path: str) -> Dict[str, Dict[str, str]]:
    """``hlo_op_names`` of the trace file ``trace.load`` reads under
    ``path``."""
    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    with open(max(files, key=os.path.getmtime), "rb") as f:
        return hlo_op_names(f.read())
