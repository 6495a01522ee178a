"""The main path's device programs, compiled for a described v5e:2x2 at
real sizes — no chip (on-chip-measurement guide, section 2).

Interpret-mode tests cannot see what the TPU compiler refuses: a
donated kernel's missing slot ref, Mosaic's missing uint8 -> f32 cast,
and VMEM overflow on wide rows all passed every CPU test before PR 21.
Each case here compiles one program and checks that the Pallas kernel
(``tpu_custom_call``) or the collective is in it.  The topology is
described inside a fixture, never at import (test workers would race
for libtpu's lock), and the persistent compile cache is off around the
compiles (a described chip's entries cannot be read back).
"""

from __future__ import annotations

import os

import pytest

MIB64_F32 = (8192, 2048)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            t = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield t
    finally:
        jax.config.update("jax_enable_compilation_cache", saved)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """Tracers carry no devices, so the transmit gate would take its
    off-TPU branch; steer it here, in the test."""
    from incubator_brpc_tpu.ops import transfer as T

    monkeypatch.setattr(T, "_on_tpu", lambda arr: True)


def _plan(shape, dtype):
    import jax

    from incubator_brpc_tpu.ops import transfer as T
    from incubator_brpc_tpu.utils.segmentation import DEVICE_CHUNK_BYTES

    v, block_rows, chunks = T.chunk_plan_for(
        jax.ShapeDtypeStruct(shape, dtype), DEVICE_CHUNK_BYTES
    )
    return v.shape, block_rows, chunks


def _kernel_case(name):
    """(fn, [(shape, dtype), ...]) for one transfer kernel at the 64 MiB
    plan of the bench's f32 (8192, 2048) frame."""
    import jax.numpy as jnp

    from incubator_brpc_tpu.ops import transfer as T

    f32 = jnp.float32
    shape, br, chunks = _plan(MIB64_F32, f32)
    lane = ((1, shape[1]), f32)
    rows = chunks[0][1]
    return {
        "whole": (T.device_copy_with_checksum, [(shape, f32)]),
        "fused": (lambda x: T._chunked_copy_csum(x, tuple(chunks), br, False),
                  [(shape, f32)]),
        "chunk": (
            lambda x, c: T.device_copy_with_checksum_chunk(x, c, br),
            [((rows, shape[1]), f32), lane],
        ),
    }[name]


def _compile(fn, args, sharding):
    import jax

    sds = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in args]
    return jax.jit(fn).lower(*sds).compile()


@pytest.mark.parametrize(
    "case",
    [
        "whole", "fused", "chunk",
        "chunked_uint8_64mib", "chunked_bf16_64mib", "chunked_int32_64mib",
        "transmit_uint8_1mib", "transmit_f32_4kib", "transmit_f32_4096x4096",
        "transmit_bf16_1024x8192", "transmit_int32_1024x4096",
        "sharded_ps_forward",
    ],
)
def test_main_path_compiles_for_v5e(case, topo, one_chip, on_tpu):
    import jax.numpy as jnp

    from incubator_brpc_tpu.ops import transfer as T

    if case == "sharded_ps_forward":
        _check_sharded_ps(topo)
        return
    if case.startswith("transmit_"):
        shape, dtype = {
            "transmit_uint8_1mib": ((1 << 20,), jnp.uint8),
            "transmit_f32_4kib": ((8, 128), jnp.float32),
            "transmit_f32_4096x4096": ((4096, 4096), jnp.float32),
            "transmit_bf16_1024x8192": ((1024, 8192), jnp.bfloat16),
            "transmit_int32_1024x4096": ((1024, 4096), jnp.int32),
        }[case]
        compiled = _compile(T.transmit_array, [(shape, dtype)], one_chip)
    elif case.startswith("chunked_"):
        # the fabric's entry at the bulk size: the size rule sends these
        # through the fused chunk program, which only f32 compiled on
        # before (uint8 is where the missing unsigned cast showed)
        from incubator_brpc_tpu.utils.segmentation import DEVICE_CHUNK_BYTES

        shape, dtype = {
            "chunked_uint8_64mib": ((64 << 20,), jnp.uint8),
            "chunked_bf16_64mib": ((8192, 4096), jnp.bfloat16),
            "chunked_int32_64mib": (MIB64_F32, jnp.int32),
        }[case]
        compiled = _compile(
            lambda x: T.transmit_array_chunked(x, DEVICE_CHUNK_BYTES),
            [(shape, dtype)], one_chip,
        )
        assert "_chunked_copy_csum" in compiled.as_text(), case
    else:
        fn, args = _kernel_case(case)
        compiled = _compile(fn, args, one_chip)
    assert "tpu_custom_call" in compiled.as_text(), f"{case}: no kernel"


def _check_sharded_ps(topo):
    """The ("slice", "chip")-sharded PsService Forward program on four
    described chips: W (8192, 8192) f32 row-sharded, a 32-row batch,
    ONE all-reduce merging the partials."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from incubator_brpc_tpu.batching.sharded import ShardedFusedKernel
    from incubator_brpc_tpu.parallel.mesh import create_mesh

    mesh = create_mesh((1, 4), devices=topo.devices)
    kern = ShardedFusedKernel(mesh, "chip")
    d = 8192
    w = jax.ShapeDtypeStruct(
        (d, d), jnp.float32, sharding=NamedSharding(mesh, P("chip", None))
    )
    x = jax.ShapeDtypeStruct(
        (32, d), jnp.float32, sharding=NamedSharding(mesh, P(None, "chip"))
    )
    compiled = kern._get_jit().lower(w, x).compile()
    text = compiled.as_text()
    assert text.count("all-reduce(") == 1, "expected ONE psum merge"
    # each chip holds a quarter of W and of the batch
    per_chip = compiled.memory_analysis().argument_size_in_bytes
    assert per_chip == (d * d + 32 * d) * 4 // 4, per_chip


def test_float16_takes_the_xla_copy_lane(on_tpu):
    """Mosaic cannot load float16 vectors: the transmit gate routes it
    to the XLA copy (no checksum) instead of a kernel that cannot
    compile."""
    import jax.numpy as jnp

    from incubator_brpc_tpu.ops import transfer as T

    x = jnp.ones((256, 2048), jnp.float16)
    assert not T.kernel_lane(x)
    out, csum = T.transmit_array(x)
    assert csum is None and bool(jnp.array_equal(out, x))
