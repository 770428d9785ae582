"""Look at a trace by hand before trusting code against it.

    python3 benchmarks/tools/dump_xplane.py <file.xplane.pb | --record DIR>
    python3 benchmarks/tools/dump_xplane.py --runs <pattern> <file.xplane.pb>...

With ``--record`` it traces a small jitted loop on this machine's device
(host and Python tracers off), prints the clocks at the start, and
leaves the trace in DIR: that is how the recorded trace beside the
tests was made. With ``--runs`` it lists every run of the programs whose
name matches beside the profile's edges (``tools/keep_trace.py`` keeps a
cell's profile): a run the profile cut is short and touches one.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def record(out: str) -> str:
    import jax
    import jax.numpy as jnp

    from benchmarks.harness import xplane

    @jax.jit
    def small_loop(x):
        def body(c, _):
            return jnp.tanh(c @ c) * 0.5, None
        return jax.lax.scan(body, x, None, length=8)[0]

    x = jnp.ones((256, 256), jnp.float32)
    jax.block_until_ready(small_loop(x))
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 0
    opts.python_tracer_level = 0
    print("clocks at start: time_ns", time.time_ns(), "monotonic_ns",
          time.monotonic_ns(), "perf_counter_ns", time.perf_counter_ns())
    jax.profiler.start_trace(out, profiler_options=opts)
    for _ in range(3):
        jax.block_until_ready(small_loop(x))
        time.sleep(0.01)
    jax.profiler.stop_trace()
    return xplane.find_xplane(out)


def dump(path: str) -> None:
    from jax.profiler import ProfileData

    print(path, os.path.getsize(path), "bytes")
    for plane in ProfileData.from_file(path).planes:
        print("plane", repr(plane.name))
        for line in plane.lines:
            events = list(line.events)
            if not events:
                continue
            first = min(e.start_ns for e in events)
            last = max(e.start_ns + e.duration_ns for e in events)
            print(f"  line {line.name!r}: {len(events)} events, "
                  f"start {first:.0f} ns, extent {(last - first) / 1e6:.3f} ms")
            by_name: dict = {}
            for e in events:
                by_name[e.name] = by_name.get(e.name, 0) + e.duration_ns
            for name, ns in sorted(by_name.items(), key=lambda kv: -kv[1])[:8]:
                print(f"    {ns / 1e6:10.3f} ms  {name[:100]}")


def runs(pattern: str, path: str) -> None:
    """Each run of the matching programs: start, length, device time of
    the operations inside, and how far it lies from the profile's first
    and last instant (all ms)."""
    from jax.profiler import ProfileData

    from benchmarks.harness import xplane

    for plane in ProfileData.from_file(path).planes:
        if plane.name == "Task Environment":
            stats = dict(plane.stats)
            print(path, "profiled for", (stats["profile_stop_time"]
                  - stats["profile_start_time"]) / 1e6, "ms")
    for plane in xplane.load(path):
        first, last = xplane.extent(plane["ops"] + plane["modules"])
        print(f"{plane['device']}: first event {first / 1e6:.3f}, last "
              f"event ends {last / 1e6:.3f}")
        for name, start, dur in xplane.matching(plane["modules"], pattern):
            inside = xplane.within(plane["ops"], [(name, start, dur)], ".")
            print(f"  {name[:40]:40s} start {start / 1e6:11.3f} length "
                  f"{dur / 1e6:9.3f} ops {len(inside):6d} busy "
                  f"{xplane.busy_seconds(inside) * 1e3:9.3f} from first "
                  f"{(start - first) / 1e6:9.3f} to last "
                  f"{(last - start - dur) / 1e6:9.3f}")


if __name__ == "__main__":
    if sys.argv[1] == "--runs":
        for path in sys.argv[3:]:
            runs(sys.argv[2], path)
    elif sys.argv[1] == "--record":
        dump(record(sys.argv[2]))
    else:
        dump(sys.argv[1])
