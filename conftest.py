"""Repo-level pytest config: make ``src`` importable and stub optional deps.

The container image has no ``hypothesis``; the property tests degrade to a
deterministic sampled sweep via ``tests/_hypothesis_stub.py`` (the real
package is used whenever it is installed).
"""
import os
import sys

_ROOT = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_ROOT, "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from tests._hypothesis_stub import install as _install_hypothesis_stub  # noqa: E402

_install_hypothesis_stub()

import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_per_module():
    """Release compiled executables between test modules.

    jaxlib's CPU client keeps every JIT'd executable mmap'd for the life of
    the process (~190 mappings per pipeline-sized test).  A full-suite run
    crosses the kernel's ``vm.max_map_count`` default (65530) around test
    ~310 and LLVM's JIT segfaults on the failed mmap inside
    ``backend_compile``.  Clearing per module bounds the map count at the
    largest single module while keeping within-module compile caching.
    """
    yield
    import jax

    jax.clear_caches()
