import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
_BENCH = os.path.dirname(_HERE)
for p in (os.path.dirname(_BENCH), _BENCH, _HERE):
    if p not in sys.path:
        sys.path.insert(0, p)
