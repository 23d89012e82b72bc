"""One generator per kind of load; a traffic file names its generator.

Each generator module has ``setup(cfg, traffic, seed, devices, log)``,
which builds the deployment through the program's public API, loads it
and warms every shape its load uses; ``run(state, seconds, probe, log)``,
the measured window; ``check(state, result, log)``, which frees the
program's state and compares a sample of the answers with the plain
reference; and ``control(state, result, log)``, the same comparison
with the reference in the next lower precision in the program's place.
"""
