# Developer entry points.  CI runs `make check` + the tier-1 pytest
# invocation (ROADMAP.md); the sanitizer and witness lanes are the
# deeper, slower sweeps.

PY ?= python

.PHONY: check test sanitize sanitize-tsan witness witness-device graph \
	inventory device-census bench-ici

# correctness gate, three passes: lock discipline + project invariants
# + device-plane discipline (host-sync/transfer/retrace/donation rules)
check:
	$(PY) tools/check.py --all

test:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m 'not slow'

# ASan+UBSan native build + the needs_native lane (docs/analysis.md)
sanitize:
	tools/sanitize.sh asan

# ThreadSanitizer over the mux/worker threads
sanitize-tsan:
	tools/sanitize.sh tsan

# full tier-1 with every package lock wrapped in the runtime witness;
# the session cross-checks acquisition orders against lock_order.json
witness:
	BRPC_LOCK_WITNESS=1 JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m 'not slow'

# full tier-1 under the armed transfer guard: every unmanifested
# device→host pull from package code fails the lane, and FusedKernel
# retraces are cross-checked against their padding-bucket bounds
witness-device:
	BRPC_TRANSFER_WITNESS=1 JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m 'not slow'

graph:
	$(PY) tools/check.py --dump-graph

inventory:
	$(PY) tools/check.py --dump-inventory

device-census:
	$(PY) tools/check.py --dump-device-census

# the ICI data-plane segments only: the 64MB headline echo and the
# resharding bulk-move collective-step proof (docs/ici_pipeline.md)
bench-ici:
	$(PY) -c "import json, bench; \
	print(json.dumps({**bench.bench_ici_rpc(), \
	**bench.bench_resharding_bulk_move()}, indent=2))"
