"""repro: a JAX system reproducing and extending

    "A High Performance Implementation of Spectral Clustering on CPU-GPU
     Platforms" (Jin & JaJa, 2018)

adapted to TPUs.  See DESIGN.md for the system inventory.

Subsystems are importable as ``repro.core``, ``repro.kernels``,
``repro.sparse``, ``repro.serve``, ``repro.launch`` etc.  We intentionally do
NOT eagerly import jax-heavy modules here so that ``import repro`` stays cheap
and never touches jax device state (a test that fakes a device count sets its
environment before jax starts).
"""

__version__ = "0.1.0"
