"""The chip benchmark of the STwig matcher.

``python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace
<0|1>`` runs one cell of ``BENCHMARK.json`` on the chip and prints one JSON
line. Everything that measures lives here, apart from the program under
test: graph and traffic generation, the plain reference that decides
``correct``, the reduction of profiler traces, the table of peaks and the
byte counts of kernels. A configuration, a traffic mix and a per-layer
metric are each a file of their own, found by name:

    bench/configs/<config>.json    graph deployment (sizes, guarantees)
    bench/traffic/<traffic>.json   traffic mix (templates, loop, rate)
    bench/metrics/<metric>.py      per-layer reader, ``read(run)``
"""
