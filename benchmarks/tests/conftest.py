"""The benchmark's own tests run on the CPU.  The setting must precede
JAX's import; nothing here describes a TPU (a test that does, does so
inside a fixture).  The runner's rehearsals are processes of their own
and make their own virtual devices."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)
