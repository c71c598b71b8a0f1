# Build/CI entry points (the reference's L10: sbt projects + run-tests.sh
# + travis matrix, SURVEY.md §1). Everything runs from a bare checkout.

PY ?= python

.PHONY: test test-fast native lint dryrun all

all: native test

test:
	$(PY) -m pytest tests/ -q

test-fast:
	$(PY) -m pytest tests/ -q -x -m "not slow"

# repo-invariant static analysis (tools/tfslint): lock discipline,
# telemetry-registry parity, config env/docs parity, thread/reset
# hygiene, fault typing, export/docs parity. Pure stdlib — no deps.
lint:
	$(PY) -m tools.tfslint tensorframes_tpu/

# C++ runtime: GraphDef parser, conversion kernels, PJRT host
native:
	$(MAKE) -C native

# driver entry points: single-chip compile check + virtual multi-chip dry run
dryrun:
	$(PY) -c "import __graft_entry__ as g; fn, a = g.entry(); import jax; jax.jit(fn)(*a); print('entry ok')"
	$(PY) -c "import __graft_entry__ as g; g.dryrun_multichip(8); print('dryrun ok')"
