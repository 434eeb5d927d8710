# gatekeeper-tpu build/test targets.
# Reference analogue: the reference Makefile's native-test / test /
# manager / deploy; the engine here is jax so "manager" is a python entry
# point.  The benchmark is benchmark/run.py (see BENCHMARK.json).

IMG ?= gatekeeper-tpu:latest
PY ?= python

.PHONY: all native-test test soak probe demo demo-basic demo-agilebank manager worker \
        docker-build deploy undeploy lint ci

all: test

# unit + integration tests on a virtual 8-device CPU mesh (conftest.py
# forces jax_platforms=cpu; the reference's native-test is `go test ./...`)
native-test:
	$(PY) -m pytest tests/ -q

test: native-test

# long-running fuzz + race soak sweeps (tests/test_soak.py gates on
# GATEKEEPER_SOAK=1 so the default suite stays fast).  Cadence: run
# before cutting a release image and nightly in CI — see ci.sh.
soak:
	GATEKEEPER_SOAK=1 $(PY) -m pytest tests/test_soak.py -q

# self-validate both engines via the framework's Probe
# (client/probe.py — the reference's probe_client readiness surface)
probe:
	$(PY) -m gatekeeper_tpu.client.probe

# demo/basic flow end-to-end (1k namespaces + required-labels template)
demo:
	$(PY) -m gatekeeper_tpu.cmd.manager --demo --port -1

# demo/basic: the reference's scripted walkthrough with its fixture tree
demo-basic:
	$(PY) demo/basic/demo.py

# demo/agilebank: multi-policy scenario with inventory join + audit
demo-agilebank:
	$(PY) demo/agilebank/demo.py

manager:
	$(PY) -m gatekeeper_tpu.cmd.manager

worker:
	$(PY) -m gatekeeper_tpu.cmd.worker

docker-build:
	docker build -t $(IMG) .

# reference Makefile:48 `deploy` applies the manifest
deploy:
	kubectl apply -f deploy/gatekeeper-tpu.yaml

undeploy:
	kubectl delete -f deploy/gatekeeper-tpu.yaml

lint:
	$(PY) -m compileall -q gatekeeper_tpu

ci: lint native-test
