set -x
W=mimo-v2-flash-serve.mixed-len-decode
timeout 2000 python3 benchmarks/run.py --workload $W --seed 2147483651 --seconds 30 --trace 1 > chiprun_out/mimo_t1.log 2> chiprun_out/mimo_t1.err
echo rc=$?
python3 benchmarks/tools/kernel_names.py benchmarks_out/$W/trace > chiprun_out/mimo_kernels.txt 2>&1
tail -c 9000 chiprun_out/mimo_t1.log
tail -c 3000 chiprun_out/mimo_t1.err
head -30 chiprun_out/mimo_kernels.txt
