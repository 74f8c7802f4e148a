"""The benchmark's own tests run on the CPU, at a tiny registry, with
the node's host verifier (or the control, or a planted fault) in the
device verifier's place.  Run from the repository root:

    python3 -m pytest benchmark/tests -q
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
