# one set of six runs of the new cell, a process and a seed each
python3 benchmarks/tools/sets.py mimo-v2-flash-serve.mixed-len-decode 30 pr27a 0 2147483659 3141592653 2718281828 4000000063 1234567891 3999999979
