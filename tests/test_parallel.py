"""Mesh/collective lowering tests on a virtual 8-device CPU mesh —
the multi-chip sharding path without TPU pods (SURVEY.md §4 approach)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp


@pytest.fixture(scope="module")
def cpu_mesh():
    from incubator_brpc_tpu.parallel.mesh import create_mesh

    devs = jax.devices("cpu")
    if len(devs) < 8:
        pytest.skip("need 8 virtual cpu devices (xla_force_host_platform_device_count)")
    return create_mesh((2, 4), devices=devs[:8])


def test_mesh_and_topology(cpu_mesh):
    from incubator_brpc_tpu.parallel.mesh import ici_endpoints, device_of

    eps = ici_endpoints(cpu_mesh)
    assert len(eps) == 8
    assert str(eps[0]) == "ici://slice0/chip0"
    assert device_of(cpu_mesh, eps[5]) is cpu_mesh.devices[1][1]


def test_parallel_merge_psum(cpu_mesh):
    from incubator_brpc_tpu.parallel import collectives as C

    x = jnp.arange(8 * 4, dtype=jnp.float32).reshape(8, 4)
    out = C.parallel_merge(cpu_mesh, "chip", "sum")(x)
    expect = np.asarray(x).reshape(4, 2, 4).sum(axis=0)
    assert np.allclose(out, expect)
    out = C.parallel_merge(cpu_mesh, "chip", "max")(x)
    assert np.allclose(out, np.asarray(x).reshape(4, 2, 4).max(axis=0))


def test_all_gather_merge(cpu_mesh):
    from incubator_brpc_tpu.parallel import collectives as C

    x = jnp.arange(8 * 4, dtype=jnp.float32).reshape(8, 4)
    out = C.parallel_broadcast_gather(cpu_mesh, "chip")(x)
    assert np.allclose(out, x)


def test_ring_stream(cpu_mesh):
    from incubator_brpc_tpu.parallel import collectives as C

    x = jnp.arange(8 * 4, dtype=jnp.float32).reshape(8, 4)
    out = np.asarray(C.ring_stream(cpu_mesh, "chip")(x)).reshape(4, 2, 4)
    expect = np.asarray(x).reshape(4, 2, 4).sum(axis=0)
    for node in range(4):
        assert np.allclose(out[node], expect)


def test_partition_reshard(cpu_mesh):
    from incubator_brpc_tpu.parallel import collectives as C

    x = jnp.arange(16 * 8, dtype=jnp.float32).reshape(16, 8)
    out = C.partition_reshard(cpu_mesh, "chip")(x)
    assert out.shape == (64, 2)


def test_hedged_first_valid(cpu_mesh):
    from incubator_brpc_tpu.parallel import collectives as C

    x = jnp.arange(8 * 4, dtype=jnp.float32).reshape(8, 4)
    valid = jnp.array([0, 0, 1, 1], jnp.float32).repeat(2)
    out = C.hedged_first_valid(cpu_mesh, "chip")(x, valid)
    assert np.allclose(out, np.asarray(x)[4:6])  # first valid = chip 2


def test_training_step_sharded(cpu_mesh):
    from incubator_brpc_tpu.models.parameter_server import make_training_step

    step, params, x = make_training_step(cpu_mesh, dim=64, batch=8)
    p1, loss1 = step(params, x)
    p2, loss2 = step(p1, x)
    assert np.isfinite(float(loss1)) and np.isfinite(float(loss2))
    assert float(loss2) < float(loss1)  # it learns


def test_graft_entry_single():
    import sys

    sys.path.insert(0, "/root/repo")
    import __graft_entry__ as g

    fn, args = g.entry()
    out = jax.jit(fn)(*args)
    jax.block_until_ready(out)
    merged, csum = out
    assert merged.shape == (2048,)


def test_graft_dryrun_multichip():
    import sys

    sys.path.insert(0, "/root/repo")
    import __graft_entry__ as g

    # no device-count guard: the dryrun re-execs into a child that
    # creates its own 8 virtual CPU devices regardless of this process
    g.dryrun_multichip(8)


def test_graft_dryrun_survives_foreign_backend_env():
    """Regression for the round-1/2 red multichip gate: the driver imports
    jax (backends NOT initialized) with env selecting a non-CPU platform,
    then calls dryrun_multichip. JAX_PLATFORMS is captured at jax import,
    so an inline os.environ update can never redirect to CPU — the fix
    must re-exec in a scrubbed child whenever jax is in sys.modules."""
    import os
    import subprocess
    import sys

    env = {
        k: v
        for k, v in os.environ.items()
        if k not in ("JAX_PLATFORMS", "XLA_FLAGS", "JAX_PLATFORM_NAME")
    }
    # Simulate the driver: a platform name that is NOT cpu is already
    # latched by the time dryrun_multichip runs.  If the inline path is
    # taken, jax will try (and fail) to initialize this platform.
    env["JAX_PLATFORMS"] = "nonexistent_tpu_like_platform"
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = (
        f"import sys; sys.path.insert(0, {repo_root!r})\n"
        "import jax  # imported, backends untouched - the driver's state\n"
        "import __graft_entry__ as g\n"
        "g.dryrun_multichip(8)\n"
        "print('DRIVER_SIM_OK')\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code],
        env=env,
        capture_output=True,
        text=True,
        timeout=1500,  # must exceed the dryrun child's own 1200s budget
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "DRIVER_SIM_OK" in proc.stdout


def test_ops_merge():
    from incubator_brpc_tpu.ops import merge

    stacked = jnp.arange(3 * 4, dtype=jnp.float32).reshape(3, 4)
    assert np.allclose(merge.merge_sum(stacked), np.asarray(stacked).sum(0))
    assert np.allclose(merge.merge_max(stacked), np.asarray(stacked).max(0))
    out = merge.merge_first_valid(stacked, jnp.array([0.0, 1.0, 1.0]))
    assert np.allclose(out, np.asarray(stacked)[1])
    cat = merge.merge_concat([stacked, stacked])
    assert cat.shape == (6, 4)
