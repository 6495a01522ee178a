"""Rehearsal of chip_smoke.py on the CPU test mesh at tiny sizes.

The phase functions run as the chip runs them, through the normal
Server/Channel entry points; the transmit kernels run through the
Pallas interpreter (the platform gate is steered here, in the test).
What only a chip can show stays in chip_smoke.main(), which must refuse
to run here.
"""

from __future__ import annotations

import functools
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402


@pytest.fixture
def interpret_kernels(monkeypatch):
    """Every transmit kernel through the Pallas interpreter, the DMA
    kernel's donated-slot flavor included; small fabric chunks so a
    512 KiB frame takes each chunk mode's multi-chunk path."""
    import jax.numpy as jnp

    from incubator_brpc_tpu.ops import transfer as T
    from incubator_brpc_tpu.parallel.ici import get_fabric

    chunked = T._chunked_copy_csum
    chunk = T.device_copy_with_checksum_chunk

    def dma_into(x, slot, block_rows, stage_rows):
        carry = jnp.zeros((1, x.shape[1]), jnp.float32)
        out, acc = T._dma_call(x, carry, block_rows, stage_rows, True,
                               slot=slot)
        return out, jnp.sum(acc)

    monkeypatch.setattr(T, "_on_tpu", lambda arr: True)
    monkeypatch.setattr(
        T, "device_copy_with_checksum",
        functools.partial(T.device_copy_with_checksum, interpret=True),
    )
    monkeypatch.setattr(
        T, "_chunked_copy_csum",
        lambda x, chunks, block_rows, interpret: chunked(
            x, chunks=chunks, block_rows=block_rows, interpret=True
        ),
    )
    monkeypatch.setattr(
        T, "device_copy_with_checksum_chunk",
        lambda x, acc, br, interpret=False: chunk(x, acc, br, True),
    )
    monkeypatch.setattr(
        T, "device_copy_with_checksum_chunk_into",
        lambda x, acc, slot, br: chunk(x, acc, br, True),
    )
    monkeypatch.setattr(
        T, "device_copy_with_checksum_dma",
        functools.partial(T.device_copy_with_checksum_dma, interpret=True),
    )
    monkeypatch.setattr(T, "device_copy_with_checksum_dma_into", dma_into)
    fabric = get_fabric()
    saved = fabric.chunk_bytes
    fabric.chunk_bytes = 64 << 10
    yield
    fabric.chunk_bytes = saved


def test_ici_echo_phase_every_chunk_mode(interpret_kernels):
    import jax

    out = chip_smoke.phase_ici_echo(jax.devices()[0], shape=(512, 256),
                                    echoes=2)
    assert set(out["modes"]) == set(chip_smoke.CHUNK_MODES)
    for mode, m in out["modes"].items():
        assert m["pallas_fallbacks"] == 0, (mode, m)
        assert m["unchecked_segments"] == 0, (mode, m)
    assert out["modes"]["pallas"]["pallas_frames"] == 4
    assert out["modes"]["pallas"]["ring_hits"] > 0
    assert out["modes"]["pipelined"]["ring_hits"] > 0
    assert out["ici_4k_echo"] and out["native_tcp_4k_echo"]


def test_hbm_cache_phase(interpret_kernels):
    import jax

    out = chip_smoke.phase_hbm_cache(
        jax.devices()[0], n_values=16, value_bytes=4096, sample_every=4
    )
    assert out["values"] == 18 and out["read_back"] == 6
    assert out["hbm_bytes"] == 16 * 4096 + sum(chip_smoke.CACHE_ODD_BYTES)
    # every value reads back exact, the odd lengths (two size classes of
    # their own) too; a GET reply is the store's row slice, handed off,
    # so no hop copies it on either transmit lane
    assert out["odd_reads"] == 2
    assert out["unchecked_segments"] == 0


def test_ps_forward_phase_is_exact():
    import jax

    y, out = chip_smoke.phase_ps_forward(jax.devices()[0], dim=256, rows=8)
    assert y.shape == (8, 256)
    assert out["batches"] >= 1 and out["sharded"] is False


def test_four_chip_phase_on_virtual_devices():
    import jax

    devices = jax.devices()
    if len(devices) < 8:
        pytest.skip("conftest provides 8 virtual CPU devices")
    y1, _ = chip_smoke.phase_ps_forward(devices[0], dim=256, rows=8)
    out = chip_smoke.phase_four_chips(
        devices[:4], shape=(512, 256), dim=256, rows=8, one_chip_y=y1
    )
    assert out["server_devices"] == [str(d) for d in devices[:4]]
    assert out["sharded"] and out["batches"] >= 1
    assert out["executions"] == out["collective_merges"] == out["batches"]


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_refuses_without_a_tpu(argv, capsys):
    assert chip_smoke.main(argv) != 0
    assert capsys.readouterr().out == ""
