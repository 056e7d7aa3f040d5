"""The benchmark's own tests run on the CPU, by hand:

    python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from auron_tpu.jaxenv import force_cpu_backend  # noqa: E402

force_cpu_backend(8)
