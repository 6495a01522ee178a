"""Test harness configuration.

Mirrors the reference's "real stack in one process" philosophy
(SURVEY.md §4): RPC tests run a real client + real server over loopback
TCP; mesh/collective tests run on a virtual 8-device CPU mesh so the
multi-chip sharding path is exercised without TPU pods.
"""

import os

# Must be set before jax is imported anywhere in the test process.
# Unconditional assignment: tests always run on the virtual 8-device CPU
# mesh, even on a machine with a TPU — a chip belongs to one process at
# a time, and test workers would contend for it.  The chip is driven by
# chip_smoke.py and bench.py, one process each.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Lock-witness mode (analysis/witness.py): BRPC_LOCK_WITNESS=1 wraps
# every lock the package creates in a recording proxy BEFORE any test
# imports package modules, so the suite's actual acquisition orders are
# captured and cross-checked against the static lock-order manifest at
# session end (report path: $BRPC_LOCK_WITNESS_REPORT).
if os.environ.get("BRPC_LOCK_WITNESS"):
    from incubator_brpc_tpu.analysis import witness as _witness

    _witness.enable()

# Transfer-witness mode (analysis/device_witness.py): BRPC_TRANSFER_
# WITNESS=1 arms jax's device→host transfer guard plus the package-
# callsite numpy guard BEFORE any test imports package hot paths, so
# tier-1 runs with every unmanifested device→host pull failing loudly
# and FusedKernel retraces cross-checked against their bucket bounds.
if os.environ.get("BRPC_TRANSFER_WITNESS"):
    from incubator_brpc_tpu.analysis import device_witness as _dwitness

    _dwitness.enable()

import pytest  # noqa: E402


def pytest_sessionfinish(session, exitstatus):
    if os.environ.get("BRPC_LOCK_WITNESS"):
        from incubator_brpc_tpu.analysis import witness

        path = os.environ.get(
            "BRPC_LOCK_WITNESS_REPORT", ".lock_witness_report.json"
        )
        result = witness.write_report(path)
        print(
            f"\nlock-witness: {result['witnessed_sites']} sites, "
            f"{result['checked']} mapped edges, "
            f"{len(result['new_edges'])} unmanifested, "
            f"{len(result['contradictions'])} contradiction(s) -> {path}"
        )
        for c in result["contradictions"]:
            print(f"lock-witness CONTRADICTION: {c}")
        if result["contradictions"] and session.exitstatus == 0:
            # a runtime-proven inversion must fail the lane (`make
            # witness`), not just print; wrap_session returns
            # session.exitstatus AFTER this hook runs
            session.exitstatus = 3
    if os.environ.get("BRPC_TRANSFER_WITNESS"):
        from incubator_brpc_tpu.analysis import device_witness

        path = os.environ.get(
            "BRPC_TRANSFER_WITNESS_REPORT", ".transfer_witness_report.json"
        )
        result = device_witness.write_report(path)
        bad = result["violations"] + result["retrace_contradictions"]
        print(
            f"\ntransfer-witness: {sum(result['scope_uses'].values())} "
            f"manifested pulls over {len(result['scope_uses'])} scope(s), "
            f"{len(result['kernels'])} bounded kernel(s), "
            f"{len(result['violations'])} violation(s), "
            f"{len(result['retrace_contradictions'])} retrace "
            f"contradiction(s) -> {path}"
        )
        for v in bad:
            print(f"transfer-witness CONTRADICTION: {v}")
        if bad and session.exitstatus == 0:
            # violations recorded but swallowed by handler except-blocks
            # must still fail `make witness-device`
            session.exitstatus = 3


@pytest.fixture
def free_port():
    import socket

    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port

