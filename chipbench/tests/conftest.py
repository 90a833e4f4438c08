"""chipbench's own tests: run by hand, not part of tier-1:

    JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests -q -p no:cacheprovider
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
