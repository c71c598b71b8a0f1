"""Test fixture: force an 8-device virtual CPU mesh before any backend
initializes.

Mirrors the reference's test strategy of simulating the cluster locally
(`local[1]` SparkContext with 4 shuffle partitions,
`TensorFlossTestSparkContext.scala:14-22`): multi-chip behavior runs on
virtual CPU devices; the real chip is exercised by `chip_smoke.py`.

Run with ``JAX_PLATFORMS=cpu``. The config update below says the same
in code, so a run that forgets the variable still never initializes a
hardware backend.
"""

# Force the CPU platform BEFORE importing the project package: the
# package __init__ pulls in jax, and if any module ever did
# backend-initializing work at import time it must land on CPU.
import jax

jax.config.update("jax_platforms", "cpu")

from tensorframes_tpu.utils.virtual_mesh import force_virtual_cpu_devices

force_virtual_cpu_devices(8)

import pytest


@pytest.fixture(autouse=True)
def _reset_telemetry():
    """Span/metric state never leaks across tests (the telemetry
    analogue of the `reset_stats()` discipline stats-asserting tests
    already follow): every test ends with a full `telemetry.reset()` —
    spans, counters, gauges, histograms — so a test that asserts on the
    ring or the registry always starts from the previous test's reset.
    Fault state resets with it: a chaos test's device evictions
    (circuit breakers are process-global) and ledger counts must never
    bleed into the next test's scheduling."""
    yield
    from tensorframes_tpu import config, globalframe, serving
    from tensorframes_tpu.graph import plan, vectorize
    from tensorframes_tpu.runtime import (
        autotune,
        bindings,
        blackbox,
        checkpoint,
        costmodel,
        deadline,
        faults,
        materialize,
    )
    from tensorframes_tpu.runtime.scheduler import device_health
    from tensorframes_tpu.utils import telemetry

    autotune.reset()  # a test's tuning loop/decisions never outlive it
    config.reset_tuning()  # tuned knobs revert to their defaults
    serving.reset()  # before telemetry: lanes may still emit counters
    telemetry.reset()
    faults.reset_ledger()
    device_health().reset()
    costmodel.reset()
    deadline.reset()
    checkpoint.reset_state()  # durable-stream accounting never leaks
    globalframe.reset_state()  # SPMD dispatch/fallback ledger never leaks
    materialize.reset_state()  # cached results never answer another test
    bindings.reset_state()  # one test's placed copies never serve another
    vectorize.reset_state()  # lowering/fallback ledger never leaks
    blackbox.reset_state()  # one test's incidents never explain another's
    plan.reset_state()  # rewrite/fallback/pushdown ledger never leaks
