"""Print the layout of a profiler trace: planes, lines, event counts, time
ranges and the most frequent event names.  For reading a chip trace by hand
before changing ``bench/trace_reduce.py``.

    python3 bench/trace_inspect.py <trace dir written by --keep-trace>
"""

import collections
import os
import sys

sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(log_dir: str) -> None:
    import jax

    from bench import trace_reduce

    path = trace_reduce.find_xplane(log_dir)
    print(f"{path}: {os.path.getsize(path)} bytes")
    profile = jax.profiler.ProfileData.from_file(path)
    for plane in profile.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines")
        for line in lines:
            events = list(line.events)
            if not events:
                print(f"  line {line.name!r}: 0 events")
                continue
            lo = min(e.start_ns for e in events)
            hi = max(e.start_ns + e.duration_ns for e in events)
            names = collections.Counter(e.name for e in events)
            print(f"  line {line.name!r}: {len(events)} events, "
                  f"[{lo:.0f}, {hi:.0f}] ns, busy sum "
                  f"{sum(e.duration_ns for e in events) * 1e-9:.4f} s")
            for name, n in names.most_common(6):
                print(f"    {n:7d} x {name[:120]}")
    print("bench.window:", trace_reduce.host_events(profile, "bench.window"))


if __name__ == "__main__":
    main(sys.argv[1])
