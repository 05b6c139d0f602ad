"""What a profile holds: planes, lines, the commonest event names of each and its first events.
    python3 benchmarks/tools/dump_xplane.py benchmarks_out/<workload>/trace"""
import glob, sys, collections
import jax
p = sorted(glob.glob(sys.argv[1] + "/plugins/profile/*/*.xplane.pb"))[-1]
pd = jax.profiler.ProfileData.from_file(p)
for pl in pd.planes:
    print("PLANE", pl.name)
    for ln in pl.lines:
        evs = list(ln.events)
        print("  LINE", repr(ln.name), len(evs))
        names = collections.Counter(e.name[:100] for e in evs)
        for n, c in names.most_common(12):
            print("      ", c, n)
        for e in evs[:3]:
            print("     EV", e.name[:200], e.start_ns, e.duration_ns, {k: str(v)[:80] for k, v in list(e.stats)[:12]})
