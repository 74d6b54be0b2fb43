"""The benchmark's tests run on the CPU: pin it before JAX is imported."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
