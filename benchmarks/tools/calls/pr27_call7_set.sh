# one traced run, then a set of six plain runs of the new cell, a process and a seed each
W=mimo-v2-flash-serve.mixed-len-decode
python3 benchmarks/tools/sets.py $W 30 pr27t 1 2147483651
python3 benchmarks/tools/kernel_names.py benchmarks_out/$W/trace | head -12
python3 benchmarks/tools/sets.py $W 30 pr27b 0 2147483659 3141592653 2718281828 4000000063 1234567891 3999999979
