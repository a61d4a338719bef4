"""Cut a profiler dump to what obs/trace_attr.py reads, as test data:

    python tests/data/cut_xplane.py IN.xplane.pb OUT.xplane.pb.gz

Keeps, re-encoded in the same wire format: each device plane's "XLA
Modules" and "XLA Ops" lines with the event metadata they use (name,
and of the stats only ``tf_op`` and ``program_id``); of the host planes
the ``lgbm/`` annotations alone; of ``/host:metadata`` each program with
its HLO reduced to instruction names and their ``metadata.op_name``.
Python-tracer events, the runtime's own threads, shapes, operands and
every other stat go. tests/data/chip_two_chunks.xplane.pb.gz was cut
from a dump of two sampled chunks at 2,000,000 rows x 13 taken on a v5e
chip by benchmark/tests/trace_chip.py (its cost phase's profiler turn).
"""
import gzip
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from lightgbm_tpu.obs.trace_attr import _fields, _text  # noqa: E402

KEEP_STATS = ("tf_op", "program_id", "Hlo Proto")
KEEP_LINES = ("XLA Modules", "XLA Ops")


def varint(x: int) -> bytes:
    out = bytearray()
    while True:
        b = x & 0x7F
        x >>= 7
        if x:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def field(num: int, value) -> bytes:
    if isinstance(value, int):
        return varint(num << 3) + varint(value)
    return varint(num << 3 | 2) + varint(len(value)) + value


def cut_hlo(hlo_proto: bytes) -> bytes:
    """HloProto -> HloProto whose instructions keep name and op_name."""
    out = b""
    for fnum, _wt, module in _fields(hlo_proto):
        if fnum != 1:
            continue
        mod = b""
        for f2, _w2, v2 in _fields(module):
            if f2 == 1:
                mod += field(1, v2)
            elif f2 == 3:
                comp = b""
                for f3, _w3, v3 in _fields(v2):
                    if f3 == 1:
                        comp += field(1, v3)
                    elif f3 == 2:
                        ins = b""
                        for f4, _w4, v4 in _fields(v3):
                            if f4 == 1:
                                ins += field(1, v4)
                            elif f4 == 7:
                                meta = b"".join(
                                    field(2, v5) for f5, _w5, v5
                                    in _fields(v4) if f5 == 2)
                                ins += field(7, meta)
                        comp += field(2, ins)
                mod += field(3, comp)
        out += field(1, mod)
    return out


def cut_plane(plane: bytes) -> bytes:
    name, lines, metas, stat_names = "", [], {}, {}
    for fnum, _wt, v in _fields(plane):
        if fnum == 2:
            name = _text(v)
        elif fnum == 3:
            lines.append(v)
        elif fnum == 4:
            key = next(v2 for f2, _w, v2 in _fields(v) if f2 == 1)
            metas[key] = next(v2 for f2, _w, v2 in _fields(v) if f2 == 2)
        elif fnum == 5:
            key = next(v2 for f2, _w, v2 in _fields(v) if f2 == 1)
            val = next(v2 for f2, _w, v2 in _fields(v) if f2 == 2)
            stat_names[key] = _text(next(
                (v3 for f3, _w, v3 in _fields(val) if f3 == 2), b""))
    device = "/device:" in name
    meta_names = {k: _text(next((v2 for f2, _w, v2 in _fields(m)
                                 if f2 == 2), b""))
                  for k, m in metas.items()}
    out = field(2, name.encode())
    used = set(metas) if name == "/host:metadata" else set()
    for ln in lines:
        lname = next((_text(v) for f, _w, v in _fields(ln) if f == 2), "")
        if device and lname not in KEEP_LINES:
            continue
        kept = b""
        for f, _w, v in _fields(ln):
            if f != 4:
                if f in (1, 2, 3, 10, 11):
                    kept += field(f, v)
                continue
            mid = next(v2 for f2, _w2, v2 in _fields(v) if f2 == 1)
            if not device and not meta_names.get(mid, "").startswith(
                    "lgbm/"):
                continue
            used.add(mid)
            kept += field(4, b"".join(field(f2, v2) for f2, _w2, v2
                                      in _fields(v) if f2 in (1, 2, 3)))
        if b"\x22" in kept or device:        # a line with events left
            out += field(3, kept)
    used_stats = set()
    for mid in sorted(used):
        meta = b""
        for f, _w, v in _fields(metas[mid]):
            if f in (1, 2):
                meta += field(f, v)
            elif f == 5:
                sid = next(v2 for f2, _w2, v2 in _fields(v) if f2 == 1)
                if stat_names.get(sid) not in KEEP_STATS:
                    continue
                used_stats.add(sid)
                if stat_names[sid] == "Hlo Proto":
                    v = b"".join(
                        field(f2, cut_hlo(v2) if f2 == 6 else v2)
                        for f2, _w2, v2 in _fields(v))
                meta += field(5, v)
        out += field(4, field(1, mid) + field(2, meta))
    for sid in sorted(used_stats):
        out += field(5, field(1, sid) + field(2, field(1, sid) + field(
            2, stat_names[sid].encode())))
    return out


def main() -> int:
    src, dst = sys.argv[1], sys.argv[2]
    with open(src, "rb") as f:
        data = f.read()
    out = b"".join(field(1, cut_plane(v)) for fnum, _wt, v
                   in _fields(data) if fnum == 1)
    with gzip.open(dst, "wb", compresslevel=9) as f:
        f.write(out)
    print(f"{len(data)} -> {len(out)} bytes, "
          f"{os.path.getsize(dst)} gzipped")
    return 0


if __name__ == "__main__":
    sys.exit(main())
