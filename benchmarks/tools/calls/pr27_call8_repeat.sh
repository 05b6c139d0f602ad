# is the spread the seed's or the run's? the slowest and the fastest seed of
# set pr27b, twice each, traced (phase medians and device busy time a run)
python3 benchmarks/tools/sets.py mimo-v2-flash-serve.mixed-len-decode 30 pr27r 1 2147483659 3141592653 2147483659 3141592653 | grep -v BREAKDOWN
