"""Testing utilities: the deterministic fault-injection harness and the
Keras freeze recipe (`keras_freeze`).

Not imported by the library itself — test suites and `chip_smoke.py`
opt in with ``from tensorframes_tpu.testing import faults``.
"""

from . import faults  # noqa: F401

__all__ = ["faults"]
