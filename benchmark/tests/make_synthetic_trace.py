"""Writes testdata/synthetic.xplane.pb: a tiny XSpace in the profiler's wire
format whose answers are known by hand (see test_xplane.py). Run once; the
file is kept so that the reduction is checked against recorded bytes."""
import os


def varint(x: int) -> bytes:
    out = b""
    while True:
        b = x & 0x7F
        x >>= 7
        if x:
            out += bytes([b | 0x80])
        else:
            return out + bytes([b])


def field(num: int, value) -> bytes:
    if isinstance(value, int):
        return varint(num << 3) + varint(value)
    return varint(num << 3 | 2) + varint(len(value)) + value


def event(mid, off_ps, dur_ps):
    return field(1, mid) + field(2, off_ps) + field(3, dur_ps)


def line(name, ts_ns, events):
    return (field(2, name.encode()) + field(3, ts_ns)
            + b"".join(field(4, e) for e in events))


def plane(name, lines, names):
    meta = b"".join(field(4, field(1, k) + field(2, field(1, k)
                                                  + field(2, v.encode())))
                    for k, v in names.items())
    return (field(2, name.encode()) + b"".join(field(3, ln) for ln in lines)
            + meta)


US = 1_000_000  # picoseconds
device = plane("/device:TPU:0", [line("XLA Ops", 1000, [
    event(1, 20 * US, 100 * US),    # while.1      20..120 us, encloses:
    event(2, 30 * US, 30 * US),     #   hist_kernel 30..60
    event(3, 70 * US, 20 * US),     #   fusion.2    70..90
    event(2, 95 * US, 20 * US),     #   hist_kernel 95..115
    event(4, 170 * US, 50 * US),    # copy.3      170..220
    event(3, 250 * US, 10 * US),    # fusion.2    250..260, past the window
])], {1: "while.1", 2: "hist_kernel", 3: "fusion.2", 4: "copy.3"})
host = plane("/host:CPU", [line("python", 1000, [
    event(1, 0, 240 * US),          # bench/window/traced 0..240, encloses:
    event(2, 140 * US, 100 * US),   #   bench/window/sync 140..240
])], {1: "bench/window/traced", 2: "bench/window/sync"})
if __name__ == "__main__":
    path = os.path.join(os.path.dirname(__file__), "..", "testdata",
                        "synthetic.xplane.pb")
    with open(path, "wb") as f:
        f.write(field(1, device) + field(1, host))
