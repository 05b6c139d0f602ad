"""Where a Pallas kernel's name arrives in a profile: the device operations of
the newest trace under a directory whose opcode is ``custom-call``, by the
name ``trace_reduce`` gives them, with their program, count and seconds.
    python3 benchmarks/tools/kernel_names.py benchmarks_out/<workload>/trace"""
import collections
import glob
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from benchmarks.harness import trace_reduce  # noqa: E402

path = sorted(glob.glob(sys.argv[1] + "/plugins/profile/*/*.xplane.pb"))[-1]
total = collections.Counter()
count = collections.Counter()
for events in trace_reduce.load_xplane(path)["device"].values():
    for name, _, dur, module in trace_reduce.select(events, " custom-call "):
        key = (module, re.sub(r"\.\d+ ", " ", name, count=1))
        total[key] += dur
        count[key] += 1
for (module, name), seconds in total.most_common():
    print(f"{module}  {name}  x{count[(module, name)]}  {seconds:.6f} s")
