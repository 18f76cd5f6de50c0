import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402

# The tests compile for the CPU; a persistent cache of those programs
# only fills the checkout.
jax.config.update("jax_enable_compilation_cache", False)
