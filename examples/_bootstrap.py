"""Shared demo setup: the import path.

The demos run on whatever JAX finds; set ``JAX_PLATFORMS=cpu`` to run
them on the host CPU (first compile on a TPU takes tens of seconds, then
flushes are sub-millisecond).
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
