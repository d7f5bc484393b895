# Repro harness targets.  PYTHONPATH=src is baked into every target.
PY := PYTHONPATH=src$(if $(PYTHONPATH),:$(PYTHONPATH)) python

.PHONY: test test-fast test-cohort test-sharded test-service test-faults \
    test-privacy bench-engine bench-engine-smoke bench-kernels \
    bench-kernels-smoke bench-scale bench-scale-smoke bench-service \
    bench-service-smoke bench-privacy bench-privacy-smoke bench \
    quickstart examples-smoke

# tier-1 verify: the whole suite, fail-fast (matches ROADMAP.md)
test:
	$(PY) -m pytest -x -q

# engine + core only (skips the slow per-arch smoke sweep)
test-fast:
	$(PY) -m pytest -x -q tests/test_core_masking.py tests/test_kernels.py \
	    tests/test_mask_uplink.py \
	    tests/test_codecs.py tests/test_round_engine.py \
	    tests/test_scan_engine.py tests/test_fed_engine.py \
	    tests/test_experiment_api.py tests/test_history_golden.py

# streaming cohort tier: cohort ≡ scan parity, hierarchical count
# aggregation, skewed populations, 1e5-client smoke (CI job: test-cohort)
test-cohort:
	$(PY) -m pytest -x -q tests/test_cohort_engine.py \
	    tests/test_federated_skew.py

# wire-true service tier: serde round-trips, loopback sync ≡ scan parity,
# measured bytes-on-wire, async staleness goldens (CI job: test-service)
test-service:
	$(PY) -m pytest -x -q tests/test_service.py

# availability + fault-injection tier: dropout traces on every engine,
# degraded codec partials, service FaultPlan drops/corrupts/crashes/hangs
# with exact accounting (CI job: test-faults)
test-faults:
	$(PY) -m pytest -x -q tests/test_availability.py tests/test_faults.py

# distributed-DP tier: discrete mechanisms, RDP accounting, noise-once
# split invariance, five-engine DP parity, coordinator (ε, δ) metrics
# (CI job: test-privacy, run with REPRO_REQUIRE_HYPOTHESIS=1)
test-privacy:
	$(PY) -m pytest -x -q tests/test_privacy.py

# multi-device tier: 8 fake CPU devices so the pod client mesh axis and
# the shard_map seed mesh genuinely partition (CI job: test-multidevice)
test-sharded:
	XLA_FLAGS="--xla_force_host_platform_device_count=8" \
	    $(PY) -m pytest -x -q tests/test_sharded_engine.py \
	    tests/test_sharding_units.py

# looped/batched/scan round engine benchmark (ISSUE 1+2 acceptance);
# writes machine-readable BENCH_engine.json at the repo root
bench-engine:
	$(PY) -m benchmarks.run --only engine

# 1 tiny config — keeps the BENCH_engine.json emitter green in CI
bench-engine-smoke:
	$(PY) -m benchmarks.run --only engine --quick

# fused vs staged mask-uplink kernel microbench (ISSUE 6 acceptance);
# writes machine-readable BENCH_kernels.json at the repo root
bench-kernels:
	$(PY) -m benchmarks.run --only kernels

# tiny sizes — keeps the BENCH_kernels.json emitter green in CI
bench-kernels-smoke:
	$(PY) -m benchmarks.run --only kernels --quick

# cohort-streaming scale bench: clients/sec at C up to 1e6 host-resident
# clients + prefetch on/off ratio; writes BENCH_scale.json at the repo root
bench-scale:
	$(PY) -m benchmarks.run --only scale

# small populations (C <= 1e4) — keeps the BENCH_scale.json emitter green
bench-scale-smoke:
	$(PY) -m benchmarks.run --only scale --quick

# loopback-HTTP coordinator bench: service vs scan rounds/sec, measured
# bytes-on-wire, sync vs async latency; writes BENCH_service.json
bench-service:
	$(PY) -m benchmarks.run --only service

# few rounds — keeps the BENCH_service.json emitter green in CI
bench-service-smoke:
	$(PY) -m benchmarks.run --only service --quick

# measured ε/accuracy/bits trade-off of the DP count release across
# noise multipliers; writes BENCH_privacy.json at the repo root
bench-privacy:
	$(PY) -m benchmarks.run --only privacy

# fewer rounds — keeps the BENCH_privacy.json emitter green in CI
bench-privacy-smoke:
	$(PY) -m benchmarks.run --only privacy --quick

bench:
	$(PY) -m benchmarks.run --quick

quickstart:
	$(PY) examples/quickstart.py

# tiny-round example runs — keeps the Experiment-API examples green in CI
examples-smoke:
	$(PY) examples/quickstart.py --rounds 4
	$(PY) examples/fed_image_cnn.py --rounds 3 --seeds 2
	$(PY) examples/alpha_curve.py --rounds 3 --seeds 1 --alphas 0.5,5.0
