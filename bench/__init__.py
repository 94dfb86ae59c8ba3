"""Chip benchmark of the GBDT trainer and predictor (see BENCHMARK.json).

`python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>`
runs one cell on the accelerator it is started on. Configurations, traffic
mixes, traffic kinds, per-layer metric readers and correctness limits are
files found by the names in BENCHMARK.json:

    bench/configs/<config>.json        dataset shape, training parameters
    bench/traffic/<traffic>.json       the kind a mix runs, and its parameters
    bench/kinds/<kind>.py              Driver: setup(), window(s), check()
    bench/layer_metrics/<metric>.py    read(ctx) -> number or None
    bench/limits/<cell>.json           limits of the correctness numbers
    bench/peaks.json                   chip peaks keyed by device_kind

The yardstick lives here and imports nothing of the program: the dataset
generator, the numpy reference trainer and traversal, the trace reduction
and the roofline counts.
"""
