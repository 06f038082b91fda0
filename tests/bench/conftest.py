"""Put the repository root on the path so the harness imports as
``bench``, beside ``src`` which the suite's own conftest adds."""

import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)
