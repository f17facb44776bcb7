"""A reader of the few fields of a profiler's ``.xplane.pb`` that
``jax.profiler.ProfileData`` does not expose: each device operation's name
stack (the ``tf_op`` stat of its event metadata, such as
``jit(end_step)/while/body/moe/experts/dot_general``), read straight from
the protocol buffer's wire format, with no TensorFlow.

The messages (``tsl/profiler/protobuf/xplane.proto``) and the fields read:

- ``XSpace``: ``planes`` (1);
- ``XPlane``: ``name`` (2), ``lines`` (3), ``event_metadata`` (4, a map
  from id to ``XEventMetadata``), ``stat_metadata`` (5, a map from id to
  ``XStatMetadata``);
- ``XLine``: ``name`` (2), ``events`` (4);
- ``XEvent``: ``metadata_id`` (1);
- ``XEventMetadata``: ``id`` (1), ``name`` (2), ``stats`` (5);
- ``XStatMetadata``: ``id`` (1), ``name`` (2);
- ``XStat``: ``metadata_id`` (1), ``str_value`` (5), ``ref_value`` (7, the
  id of an ``XStatMetadata`` whose name is the string).
"""

from __future__ import annotations

from typing import Dict, Iterator, List, Tuple

_VARINT, _FIXED64, _BYTES, _FIXED32 = 0, 1, 2, 5


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    shift = out = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf: bytes, lo: int = 0, hi: int = -1) -> Iterator[Tuple[int, int, object]]:
    """``(field number, wire type, value)`` of each field of the message in
    ``buf[lo:hi]``: an ``int`` for a varint or a fixed-width field, the
    ``(start, end)`` of its bytes for a length-delimited one."""
    hi = len(buf) if hi < 0 else hi
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == _VARINT:
            v, i = _varint(buf, i)
        elif wt == _BYTES:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wt == _FIXED64:
            v, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wt == _FIXED32:
            v, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"wire type {wt} at byte {i} is not read")
        yield num, wt, v


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_entries(buf: bytes, span) -> Tuple[int, Tuple[int, int]]:
    """Key and value bytes of one entry of a ``map<int64, message>``."""
    key, val = 0, (span[0], span[0])
    for num, _, v in fields(buf, *span):
        if num == 1:
            key = v
        elif num == 2:
            val = v
    return key, val


def _named(buf: bytes, span) -> Tuple[int, str, List]:
    """``id``, ``name`` and the ``stats`` bytes of a metadata message."""
    mid, name, stats = 0, "", []
    for num, _, v in fields(buf, *span):
        if num == 1:
            mid = v
        elif num == 2:
            name = _text(buf, v)
        elif num == 5:
            stats.append(v)
    return mid, name, stats


def plane_names(buf: bytes) -> List[str]:
    out = []
    for num, _, span in fields(buf):
        if num == 1:
            for n, _, v in fields(buf, *span):
                if n == 2:
                    out.append(_text(buf, v))
                    break
    return out


def op_stacks(buf: bytes, plane: str, line: str = "XLA Ops",
              stat: str = "tf_op") -> List[Tuple[str, str]]:
    """``(event metadata name, name stack)`` of each event of line ``line``
    of plane ``plane``, in the order the file holds them (the order
    ``ProfileData`` gives them too); ``""`` where an operation carries no
    stack."""
    for num, _, pspan in fields(buf):
        if num != 1:
            continue
        name = next((_text(buf, v) for n, _, v in fields(buf, *pspan) if n == 2), None)
        if name != plane:
            continue
        events: Dict[int, Tuple[str, List]] = {}
        stat_names: Dict[int, str] = {}
        order: List[int] = []
        for n, _, v in fields(buf, *pspan):
            if n == 4:
                _, mspan = _map_entries(buf, v)
                mid, mname, stats = _named(buf, mspan)
                events[mid] = (mname, stats)
            elif n == 5:
                _, sspan = _map_entries(buf, v)
                sid, sname, _ = _named(buf, sspan)
                stat_names[sid] = sname
            elif n == 3:
                lname = next((_text(buf, x) for k, _, x in fields(buf, *v) if k == 2), "")
                if lname != line:
                    continue
                for k, _, espan in fields(buf, *v):
                    if k == 4:
                        order.append(next((x for j, _, x in fields(buf, *espan)
                                           if j == 1), 0))
        stack: Dict[int, str] = {}
        for mid, (mname, stats) in events.items():
            for sspan in stats:
                sid, val = 0, None
                for j, _, x in fields(buf, *sspan):
                    if j == 1:
                        sid = x
                    elif j == 5:
                        val = _text(buf, x)
                    elif j == 7:
                        val = ("ref", x)
                if stat_names.get(sid) == stat and val is not None:
                    stack[mid] = stat_names.get(val[1], "") if isinstance(val, tuple) else val
        return [(events.get(mid, ("", []))[0], stack.get(mid, "")) for mid in order]
    return []
