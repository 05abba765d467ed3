import os
import sys
import tempfile

# Make repo sources importable without install.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# The default KernelPolicy runs in degrade mode (DESIGN.md §9), which
# consults/writes the persistent plan quarantine — shield the developer's
# real ~/.cache store from the test run (tests that care pin their own
# path via KernelPolicy.tune_cache anyway).
os.environ.setdefault(
    "REPRO_QUARANTINE",
    os.path.join(tempfile.mkdtemp(prefix="repro-test-quarantine-"),
                 "quarantine.json"))

import jax  # noqa: E402

jax.config.update("jax_enable_x64", False)

