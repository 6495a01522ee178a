"""Fixtures of the benchmark's tests."""

from __future__ import annotations

import functools

import pytest

from bench_helpers import make_copy


@pytest.fixture
def tiny(tmp_path):
    return make_copy(str(tmp_path))


@pytest.fixture
def interpret_kernels(monkeypatch):
    """Every transmit kernel through the Pallas interpreter (the
    platform gate steered here, in the test, as tests/test_chip_smoke.py
    does); 64 KiB fabric chunks so a 256 KiB frame is chunked."""
    from incubator_brpc_tpu.ops import transfer as T
    from incubator_brpc_tpu.parallel.ici import get_fabric

    chunked = T._chunked_copy_csum
    chunk = T.device_copy_with_checksum_chunk
    monkeypatch.setattr(T, "_on_tpu", lambda arr: True)
    monkeypatch.setattr(
        T, "device_copy_with_checksum",
        functools.partial(T.device_copy_with_checksum, interpret=True))
    monkeypatch.setattr(
        T, "_chunked_copy_csum",
        lambda x, chunks, block_rows, interpret: chunked(
            x, chunks=chunks, block_rows=block_rows, interpret=True))
    monkeypatch.setattr(
        T, "device_copy_with_checksum_chunk",
        lambda x, acc, br, interpret=False: chunk(x, acc, br, True))
    fabric = get_fabric()
    monkeypatch.setattr(fabric, "chunk_bytes", 64 << 10)
    yield
