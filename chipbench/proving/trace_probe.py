"""Dump the structure of an xplane.pb: planes, lines, top event names, stats."""
import sys, glob, collections
import jax
for path in sys.argv[1:]:
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            names = collections.Counter(); dur = collections.Counter(); n = 0; sample = {}
            for ev in line.events:
                n += 1; names[ev.name] += 1; dur[ev.name] += ev.duration_ns
                if ev.name not in sample and len(sample) < 400:
                    sample[ev.name] = {k: (str(v)[:80]) for k, v in ev.stats}
            print("  LINE", repr(line.name), n)
            for name, d in dur.most_common(12):
                print("      %-60s n=%-7d total_ms=%.3f stats=%s" % (name[:60], names[name], d/1e6, sample.get(name)))
