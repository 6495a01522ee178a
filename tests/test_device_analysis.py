"""Device-plane discipline toolchain (analysis/devicegraph.py +
analysis/device_witness.py + tools/check.py --device): the device-site
census, the golden-finding fixtures proving each rule fires (and the
clean twin proving none misfire), the transfer manifest, the runtime
transfer/retrace witness, and the partial-mode CLI contract.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG_ROOT = os.path.join(REPO_ROOT, "incubator_brpc_tpu")
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "analysis_fixtures")

from incubator_brpc_tpu.analysis import device_witness  # noqa: E402
from incubator_brpc_tpu.analysis.devicegraph import (  # noqa: E402
    DeviceManifest,
    build_device_census,
    load_device_manifest,
    run_device_rules,
    run_dispatch_under_lock,
)
from incubator_brpc_tpu.analysis.inventory import build_inventory  # noqa: E402
from incubator_brpc_tpu.analysis.lockgraph import build_graph  # noqa: E402

HOT = ("fixture_device_hot", "fixture_device_clean")

FIXTURE_MANIFEST = DeviceManifest(
    [{"key": "fixture.known-key", "why": "clean-twin justification"}],
    path="<test>",
)


# ---------------------------------------------------------------------------
# census
# ---------------------------------------------------------------------------

def test_census_scale_and_known_sites_on_tree():
    census = build_device_census(PKG_ROOT)
    assert len(census.sites) >= 75, (
        f"device census collapsed to {len(census.sites)} sites"
    )
    kinds = {s.kind for s in census.sites}
    for expected in ("jit", "fused-kernel", "device-put", "collective",
                     "donation", "slot-acquire", "slot-release",
                     "host-sync", "allow-scope", "pallas-call"):
        assert expected in kinds, f"census never saw a {expected} site"
    # the Pallas DMA data plane is visible: transfer.py's pallas_call
    # kernels (incl. the double-buffered DMA grid) are census sites
    # (four since PR 21 removed the unused plain device_copy)
    pallas_sites = census.by_kind("pallas-call")
    assert len(pallas_sites) >= 4, pallas_sites
    assert any(s.func == "_dma_call" for s in pallas_sites), pallas_sites
    # the donation map learned ops/transfer's donating kernels, the
    # anchor of the read-after-donate rule on the real tree
    assert any("chunk_into" in name for name in census.donating), (
        census.donating
    )
    assert any("dma_into" in name for name in census.donating), (
        census.donating
    )


@pytest.fixture(scope="module")
def fx_census():
    return build_device_census(FIXTURES)


@pytest.fixture(scope="module")
def fx_findings(fx_census):
    return run_device_rules(
        fx_census, FIXTURE_MANIFEST, hot_prefixes=HOT
    )


# ---------------------------------------------------------------------------
# golden findings: every rule fires on the seeded module …
# ---------------------------------------------------------------------------

def test_fixture_host_sync_rule_fires(fx_findings):
    keys = {f.key for f in fx_findings if f.rule == "host-sync-on-hot-path"}
    assert "fixture_device_hot.py:hot_pull:asarray:0" in keys, keys
    assert "fixture_device_hot.py:hot_coerce:coerce:0" in keys, keys
    assert "fixture_device_hot.py:hot_item:item:0" in keys, keys
    assert "fixture_device_hot.py:hot_block:block:0" in keys, keys


def test_fixture_transfer_manifest_rule_fires(fx_findings):
    keys = {f.key for f in fx_findings if f.rule == "transfer-manifest"}
    assert any("fixture.unknown-key" in k for k in keys), keys


def test_fixture_raw_jit_rule_fires(fx_findings):
    keys = {f.key for f in fx_findings if f.rule == "raw-jit-retrace"}
    assert "fixture_device_hot.py:<module>:jit" in keys, keys
    assert "fixture_device_hot.py:<module>:pallas_call" in keys, keys


def test_fixture_pallas_spellings_all_censused(fx_census):
    """Bare, aliased, partial, and fully-qualified pallas_call must all
    land in the census (a spelling the census misses is a kernel the
    device rules never see)."""
    sites = [
        s for s in fx_census.by_kind("pallas-call")
        if s.module == "fixture_device_hot.py"
    ]
    details = {s.detail for s in sites}
    assert len(sites) >= 4, sites
    assert "pl.pallas_call" in details, details
    assert "bare_pallas_call" in details, details
    assert any("partial" in d for d in details), details
    assert "jax.experimental.pallas.pallas_call" in details, details


def test_fixture_slot_lifecycle_rule_fires(fx_findings):
    keys = {f.key for f in fx_findings if f.rule == "slot-lifecycle"}
    assert "fixture_device_hot.py:leaky_slot:slot" in keys, keys


def test_fixture_read_after_donate_rule_fires(fx_findings):
    keys = {f.key for f in fx_findings if f.rule == "read-after-donate"}
    assert any(k.startswith("fixture_device_hot.py:read_after_donate:buf")
               for k in keys), keys


def test_fixture_dispatch_under_lock_rule_fires():
    inv = build_inventory(FIXTURES)
    graph = build_graph(inv, root=FIXTURES)
    out = run_dispatch_under_lock(graph)
    keys = {f.key for f in out}
    assert any(k.startswith("fixture_device_hot.py:dispatch:_kernel")
               for k in keys), keys
    # … and never on the clean twin's outside-the-lock dispatch
    assert not any("fixture_device_clean" in k for k in keys), keys


# ---------------------------------------------------------------------------
# … and never on the clean twin
# ---------------------------------------------------------------------------

def test_clean_twin_trips_nothing(fx_findings):
    noise = [f for f in fx_findings if "fixture_device_clean" in f.key]
    assert noise == [], [f.format() for f in noise]


# ---------------------------------------------------------------------------
# transfer manifest
# ---------------------------------------------------------------------------

def test_manifest_rejects_blank_why():
    with pytest.raises(ValueError, match="justification"):
        DeviceManifest([{"key": "k", "why": "   "}])


def test_manifest_rejects_duplicate_key():
    with pytest.raises(ValueError, match="duplicated"):
        DeviceManifest([
            {"key": "k", "why": "a"},
            {"key": "k", "why": "b"},
        ])


def test_stale_manifest_entry_is_a_violation(fx_census):
    manifest = DeviceManifest(
        [
            {"key": "fixture.known-key", "why": "used by the clean twin"},
            {"key": "fixture.gone", "why": "stale on purpose"},
            {"key": "fixture.external", "why": "outside the scan",
             "external": True},
        ],
        path="<test>",
    )
    out = run_device_rules(fx_census, manifest, hot_prefixes=HOT)
    stale = {f.key for f in out if f.rule == "transfer-manifest-stale"}
    assert "fixture.gone" in stale, stale
    assert "fixture.known-key" not in stale
    # external entries live outside the package scan by declaration
    assert "fixture.external" not in stale


def test_checked_in_manifest_all_justified():
    m = load_device_manifest()
    assert m.entries, "device_transfers.json is empty?"
    for e in m.entries:
        assert e["why"].strip() and "TODO" not in e["why"], e


# ---------------------------------------------------------------------------
# runtime witness
# ---------------------------------------------------------------------------

@pytest.mark.skipif(
    bool(os.environ.get("BRPC_TRANSFER_WITNESS")),
    reason="the witness is armed for the whole session",
)
def test_allowed_transfer_is_noop_when_disarmed():
    assert not device_witness.enabled()
    # unknown keys are not even validated while disarmed — zero cost on
    # every un-witnessed run
    with device_witness.allowed_transfer("no-such-key"):
        pass


def _run_child(code, timeout=120):
    return subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=timeout, cwd=REPO_ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )


def test_witness_catches_seeded_unmanifested_transfer(tmp_path):
    """The lane's teeth: a package-scoped call site pulling a device
    value outside any allow scope raises and is recorded; the same pull
    under a manifested scope passes."""
    mod = tmp_path / "seeded_transfer.py"
    mod.write_text(textwrap.dedent("""\
        import numpy as np

        def pull(x):
            return np.asarray(x)

        def pull_scoped(x):
            from incubator_brpc_tpu.analysis.device_witness import (
                allowed_transfer,
            )
            with allowed_transfer("decode.token-sums"):
                return np.asarray(x)
    """))
    code = textwrap.dedent(f"""\
        import sys
        sys.path.insert(0, {str(REPO_ROOT)!r})
        from incubator_brpc_tpu.analysis import device_witness as dw
        dw.enable(extra_scopes=[{str(tmp_path)!r}])
        sys.path.insert(0, {str(tmp_path)!r})
        import seeded_transfer as st
        import jax.numpy as jnp
        x = jnp.ones((3,), jnp.float32)
        try:
            st.pull(x)
            sys.exit(4)  # the unmanifested pull was NOT caught
        except dw.TransferWitnessError:
            pass
        ok = st.pull_scoped(x)
        assert ok.shape == (3,)
        rep = dw.cross_check()
        assert len(rep["violations"]) == 1, rep
        assert rep["violations"][0]["kind"] == "transfer", rep
        assert rep["scope_uses"].get("decode.token-sums") == 1, rep
        print("WITNESS-OK")
    """)
    proc = _run_child(code)
    assert proc.returncode == 0, f"{proc.stdout}\n{proc.stderr}"
    assert "WITNESS-OK" in proc.stdout


def test_witness_rejects_unknown_scope_key():
    code = textwrap.dedent(f"""\
        import sys
        sys.path.insert(0, {str(REPO_ROOT)!r})
        from incubator_brpc_tpu.analysis import device_witness as dw
        dw.enable()
        try:
            with dw.allowed_transfer("no-such-manifest-key"):
                sys.exit(4)
        except dw.TransferWitnessError:
            print("KEY-REFUSED")
    """)
    proc = _run_child(code)
    assert proc.returncode == 0, f"{proc.stdout}\n{proc.stderr}"
    assert "KEY-REFUSED" in proc.stdout


def test_retrace_witness_flags_bound_violation():
    """A kernel whose shape family retraces past its bucket count is a
    contradiction; retraces within the bound are not."""
    code = textwrap.dedent(f"""\
        import sys
        sys.path.insert(0, {str(REPO_ROOT)!r})
        from incubator_brpc_tpu.analysis import device_witness as dw
        dw.enable()
        import jax.numpy as jnp
        from incubator_brpc_tpu.batching.fused import FusedKernel
        ok = FusedKernel(lambda x: x + 1, label="probe.ok",
                         batch_buckets=(1, 2))
        for n in (1, 2):
            ok(jnp.zeros((n, 4), jnp.float32))
        bad = FusedKernel(lambda x: x * 2, label="probe.bad",
                          batch_buckets=(1, 2))
        for n in (1, 2, 3):
            bad(jnp.zeros((n, 4), jnp.float32))
        con = dw.retrace_contradictions()
        assert len(con) == 1, con
        assert con[0]["kernel"] == "probe.bad", con
        assert con[0]["count"] == 3 and con[0]["bound"] == 2, con
        print("RETRACE-OK")
    """)
    proc = _run_child(code)
    assert proc.returncode == 0, f"{proc.stdout}\n{proc.stderr}"
    assert "RETRACE-OK" in proc.stdout


# ---------------------------------------------------------------------------
# the CLI: device pass + partial-mode staleness contract
# ---------------------------------------------------------------------------

def _run_check(*flags):
    return subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "tools", "check.py"),
         *flags, "-q"],
        capture_output=True, text=True, timeout=180, cwd=REPO_ROOT,
    )


def test_check_device_exits_zero_on_tree():
    proc = _run_check("--device")
    assert proc.returncode == 0, f"{proc.stdout}\n{proc.stderr}"


def test_check_partial_modes_do_not_promote_foreign_allowlist_entries():
    """--device alone must not report lock/invariant allowlist entries
    as stale (and vice versa): staleness for a rule is only decidable
    when the owning pass ran."""
    for flags in (("--device",), ("--locks",), ("--invariants",)):
        proc = _run_check(*flags)
        assert proc.returncode == 0, (
            f"{flags}: {proc.stdout}\n{proc.stderr}"
        )
        assert "stale-allowlist-entry" not in proc.stdout + proc.stderr, (
            f"{flags} promoted foreign allowlist entries to violations"
        )


def test_check_json_reports_device_sites(tmp_path):
    out = tmp_path / "check.json"
    proc = _run_check("--all", "--json", str(out))
    assert proc.returncode == 0, f"{proc.stdout}\n{proc.stderr}"
    payload = json.loads(out.read_text())
    assert payload["device_sites"] >= 75
    assert payload["violations"] == []
