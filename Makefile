# Tier-1 verification and benchmarks. On CPU the Pallas kernels run in the
# interpreter; `python chip_smoke.py` runs the served path on a TPU chip.

PYTHON ?= python
export PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH))

.PHONY: test test-fast bench bench-index bench-index-sharded \
	bench-index-mut bench-multiprobe bench-ingest bench-slo \
	bench-recovery bench-hash bench-kernels bench-fused-probe

test:
	$(PYTHON) -m pytest -x -q

# The CI default leg: skips the slow-marked redundant grid cells
# (tests/grids.py) — full coverage stays on `make test` / the full CI leg.
test-fast:
	$(PYTHON) -m pytest -x -q -m "not slow"

bench:
	$(PYTHON) -m benchmarks.run

bench-index:
	$(PYTHON) -m benchmarks.index_qps

bench-index-sharded:
	$(PYTHON) -m benchmarks.index_sharded

bench-index-mut:
	$(PYTHON) -m benchmarks.index_mutation

bench-multiprobe:
	$(PYTHON) -m benchmarks.index_multiprobe

bench-ingest:
	$(PYTHON) -m benchmarks.index_ingest

bench-slo:
	$(PYTHON) -m benchmarks.serving_slo

bench-recovery:
	$(PYTHON) -m benchmarks.durability

bench-hash:
	$(PYTHON) -m benchmarks.hash_throughput

bench-kernels:
	$(PYTHON) -m benchmarks.kernels

bench-fused-probe:
	$(PYTHON) -m benchmarks.fused_probe
