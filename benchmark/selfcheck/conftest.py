"""The self-check runs on the CPU: `python -m pytest benchmark/selfcheck`.

It is not part of tier-1.  It drives the benchmark's own functions at a
tiny size with the platform passed in as an argument ("cpu"); what only
a chip can show - times, rates, idle and roofline shares - is
`benchmark.run`'s to measure, on the chip.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_compilation_cache", False)
