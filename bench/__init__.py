"""The chip benchmark of the MinionS local serving stack (see BENCHMARK.json
and PERF.md).  ``python3 bench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`` runs one cell once."""
