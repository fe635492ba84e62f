"""The harness's own tests run on the CPU, with a small registry: they
drive the scheduler with stand-in backends and never need a chip."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(HERE), HERE]
