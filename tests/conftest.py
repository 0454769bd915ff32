import os
import sys

# tests run on the CPU backend (Pallas kernels in interpret mode) and never
# take an attached accelerator; a caller may still pick another platform
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
