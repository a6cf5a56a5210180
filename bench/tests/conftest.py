"""The benchmark's tests see four CPU devices, so that a cell that asks for
four chips runs its tiny version here, as the harness's by-name tests
(``tiny.workloads()``) run every cell. Set before JAX starts its backends;
tests elsewhere that start their own processes set their own count."""

import jax

try:
    jax.config.update("jax_num_cpu_devices", 4)
except RuntimeError:  # a backend already started: the count is what it is
    pass
