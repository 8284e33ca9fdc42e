"""The harness's own tests run on the CPU backend, outside tier-1:

    python -m pytest chipbench/tests -q

Nothing here is a device number: the rehearsals carry ``rehearsal_`` names.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
