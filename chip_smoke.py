#!/usr/bin/env python
"""Chip smoke: drive the main path once on a TPU through the normal
``Server`` / ``Channel`` entry points, and check what comes back.

    python chip_smoke.py            # one chip: phases 1-3
    python chip_smoke.py --chips 4  # four chips: phase 4 and its comparisons

Phases (one JSON line each, then the contract's last line
``{"ok": true, "device": {"platform", "kind", "count"}}``):

1. ``ici_echo``   — 64 MiB f32 (8192, 2048) device echoes over ICI with
   zero_copy off (the reference's rdma_performance 64 MB transfer,
   bench.py's shape; the fused chunk program), one 4 KiB ICI echo (the
   whole-frame kernel), one 4 KiB echo through the native C++ engine
   over TCP, and the two transmit kernels' checksums compared.
2. ``hbm_cache``  — 1 GiB of 1 MiB values (plus odd lengths) SET into
   ``HBMCacheService`` behind the redis front, a sample read back from
   an ICI peer as device-resident values, bit-equal to the host bytes.
3. ``ps_forward`` — ``PsService`` with a 256 MiB f32 (8192, 8192) W; a
   batched Forward of 32 rows over RPC against NumPy float64 X @ W.
4. ``four_chips`` (``--chips 4`` only) — four one-chip servers on four
   devices, a 64 MiB echo from chip 0 to chip 3, and the
   ("slice", "chip")-sharded PsService against phase 3 and NumPy.

The script is one process and starts no child that touches JAX.  It
exits non-zero at once when the first JAX device is not a TPU; a failed
check raises, and the exit code is then non-zero.  The phase functions
run anywhere (tests/test_chip_smoke.py drives them on the CPU at tiny
sizes); what only a TPU can show (every segment checksummed) is
asserted in :func:`main`.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

ECHO_SHAPE = (8192, 2048)  # 64 MiB f32
CACHE_VALUES = 1024
CACHE_VALUE_BYTES = 1 << 20
CACHE_ODD_BYTES = (1000, 4097)
PS_DIM = 8192
PS_ROWS = 32

# Forward data are small integers scaled by a power of two: every X and
# W entry is exact in bf16, every product and partial sum is exact in
# f32 (|partial| <= 8 * 8 * dim / 64 < 2**24 / 64).  So the default-
# precision TPU matmul (bf16 passes, f32 accumulation) must equal the
# float64 reference exactly, in any summation order and on any mesh —
# the tolerance is 0, and a wrong row, shard or merge cannot hide in it.
PS_INT_RANGE = 8
PS_W_SCALE = 1.0 / 64


class _CompileClock:
    """Sums JAX backend-compile seconds and counts compiles."""

    def __init__(self):
        import jax.monitoring

        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1

    def snapshot(self):
        return self.seconds, self.count


def _counter(adder) -> int:
    return int(adder.get_value())


def _echo(stub, array, timeout_ms=120000):
    from incubator_brpc_tpu.client.controller import Controller
    from incubator_brpc_tpu.protos.echo_pb2 import EchoRequest

    c = Controller()
    c.timeout_ms = timeout_ms
    c.request_attachment.append_device(array)
    stub.Echo(c, EchoRequest(message="smoke"))
    assert not c.failed(), f"echo failed: {c.error_text()}"
    arrs = c.response_attachment.device_arrays()
    assert len(arrs) == 1, f"expected one device attachment, got {len(arrs)}"
    return arrs[0]


def _check_echo(resp, x, device):
    import jax.numpy as jnp

    assert resp.devices() == {device}, f"response on {resp.devices()}"
    assert resp.shape == x.shape and resp.dtype == x.dtype
    assert bool(jnp.array_equal(resp, x)), "echo not bit-equal to request"


def _random_on(device, shape, seed):
    import jax
    import jax.numpy as jnp

    x = jax.random.normal(jax.random.key(seed), shape, jnp.float32)
    return jax.device_put(x, device)


def phase_ici_echo(device, shape=ECHO_SHAPE, echoes=3, seed=0):
    """Bulk echoes, plus the 4 KiB ICI and native TCP echoes.  Returns
    the unchecked-segment counter's delta for main() to judge."""
    from incubator_brpc_tpu import native
    from incubator_brpc_tpu.client.channel import Channel, ChannelOptions
    from incubator_brpc_tpu.client.controller import Controller
    from incubator_brpc_tpu.models.echo import EchoService, echo_stub
    from incubator_brpc_tpu.parallel import ici
    from incubator_brpc_tpu.protos.echo_pb2 import EchoRequest
    from incubator_brpc_tpu.server.server import Server, ServerOptions

    assert ici.get_fabric().zero_copy is False, "zero_copy must be off"
    x = _random_on(device, shape, seed)
    srv = Server(ServerOptions(usercode_in_dispatcher=True))
    srv.add_service(EchoService())
    assert srv.start_ici(0, 0, device=device) == 0
    ch = Channel(ChannelOptions(timeout_ms=120000, ici_device=device))
    out = {"bytes": int(x.nbytes), "echoes": echoes}
    unchecked0 = _counter(ici.ici_unchecked_segments)
    try:
        assert ch.init("ici://slice0/chip0") == 0
        stub = echo_stub(ch)
        for _ in range(echoes):
            _check_echo(_echo(stub, x), x, device)
        small = _random_on(device, (8, 128), seed + 1)  # 4 KiB
        _check_echo(_echo(stub, small), small, device)
        out["ici_4k_echo"] = True
    finally:
        ch.close()
        srv.stop()
    out["unchecked_segments"] = (
        _counter(ici.ici_unchecked_segments) - unchecked0
    )

    # the C++ engine, built from the committed source on this machine
    assert native.available(), "native engine did not build"
    nsrv = Server(ServerOptions(native_engine=True))
    nsrv.add_service(EchoService())
    assert nsrv.start(0) == 0
    nch = Channel(ChannelOptions(connection_type="native", timeout_ms=30000))
    try:
        assert nch.init(f"127.0.0.1:{nsrv.port}") == 0
        payload = bytes(range(256)) * 16
        c = Controller()
        c.request_attachment.append(payload)
        echo_stub(nch).Echo(c, EchoRequest(message="native"))
        assert not c.failed(), c.error_text()
        assert c.response_attachment.to_bytes() == payload
        out["native_tcp_4k_echo"] = True
    finally:
        nch.close()
        nsrv.stop()
    return out


def transmit_kernel_checksums(x):
    """TPU only: both transmit kernels on one frame.  Each must copy x
    exactly and give the SAME checksum (transfer.py's bit-equality
    invariant between the whole-frame kernel and the fused chunk
    program)."""
    import jax.numpy as jnp

    from incubator_brpc_tpu.ops import transfer as T
    from incubator_brpc_tpu.utils.segmentation import DEVICE_CHUNK_BYTES

    v, block_rows, chunks = T.chunk_plan_for(x, DEVICE_CHUNK_BYTES)
    runs = {
        "whole": T.device_copy_with_checksum(v),
        "fused": T._chunked_copy_csum(v, tuple(chunks), block_rows, False),
    }
    sums = {}
    for name, (out, csum) in runs.items():
        assert bool(jnp.array_equal(out, v)), f"{name} kernel copy differs"
        sums[name] = float(csum)
    assert len(set(sums.values())) == 1, f"checksums differ: {sums}"
    return sums


def batch_transmit_checksums(device):
    """TPU only: a drain batch's reply transmit (``transmit_batch``) on
    five 4 KiB f32 segments and two 4 KiB uint8 ones.  Each copy must be
    exact and each checksum the whole-frame kernel's, bit for bit.
    Returns the segments of each batch program."""
    import jax
    import jax.numpy as jnp

    from incubator_brpc_tpu.ops import transfer as T

    xs = [_random_on(device, (8, 128), 10 + k) for k in range(5)]
    xs += [jax.lax.bitcast_convert_type(_random_on(device, (1024,), 20 + k),
                                        jnp.uint8).reshape(4096)
           for k in range(2)]
    results, sizes = T.transmit_batch(xs)
    for x, (out, csum) in zip(xs, results):
        assert out is not x and bool(jnp.array_equal(out, x)), "batch copy"
        want = float(T.device_copy_with_checksum(T.lanes_view(x))[1])
        assert float(csum) == want, f"batch checksum {float(csum)} != {want}"
    return sizes


def phase_hbm_cache(device, n_values=CACHE_VALUES,
                    value_bytes=CACHE_VALUE_BYTES, odd=CACHE_ODD_BYTES,
                    sample_every=16, seed=1):
    """Load values into HBMCacheService over redis-on-ICI, read a sample
    back from an ICI peer, compare with the host bytes."""
    import numpy as np

    from incubator_brpc_tpu.cache import HBMCacheService
    from incubator_brpc_tpu.cache.store import cache_hbm_bytes
    from incubator_brpc_tpu.client.channel import Channel, ChannelOptions
    from incubator_brpc_tpu.client.controller import Controller
    from incubator_brpc_tpu.parallel import ici
    from incubator_brpc_tpu.protocols import redis as R
    from incubator_brpc_tpu.server.server import Server, ServerOptions

    blob = np.random.default_rng(seed).bytes(
        n_values * value_bytes + sum(odd)
    )
    values, off = [], 0
    for size in [value_bytes] * n_values + list(odd):
        values.append((b"k%d" % len(values), blob[off:off + size]))
        off += size
    total = off

    def rcall(ch, *cmd):
        req = R.RedisRequest()
        req.add_command(*cmd)
        resp = R.RedisResponse()
        c = Controller()
        c.timeout_ms = 120000
        ch.call_method(R.redis_method_spec(), c, req, resp)
        assert not c.failed(), c.error_text()
        return resp.reply(0)

    # the budget bounds the HBM held: slab rows round a value up to a
    # power of two, so twice the values' bytes holds them all
    svc = HBMCacheService(hbm_budget_bytes=2 * total, device=device)
    srv = Server(ServerOptions(redis_service=svc))
    assert srv.start_ici(0, 1, device=device) == 0
    ch = Channel(ChannelOptions(protocol="redis", timeout_ms=120000,
                                ici_device=device))
    hbm0 = _counter(cache_hbm_bytes)
    try:
        assert ch.init("ici://slice0/chip1") == 0
        t0 = time.perf_counter()
        for key, val in values:
            r = rcall(ch, "SET", key, val)
            assert not r.is_error(), r
        load_s = time.perf_counter() - t0
        loaded = _counter(cache_hbm_bytes) - hbm0
        assert loaded == total, f"rpc_cache_hbm_bytes {loaded} != {total}"
        sample = values[:n_values:sample_every] + values[n_values:]
        unchecked0 = _counter(ici.ici_unchecked_segments)
        for key, val in sample:
            arr = rcall(ch, "GET", key).device_array()
            assert arr is not None, f"{key!r} came back without a device ref"
            assert arr.devices() == {device}, f"{key!r} on {arr.devices()}"
            assert np.asarray(arr).tobytes() == val, f"{key!r} differs"
        unchecked = _counter(ici.ici_unchecked_segments) - unchecked0
    finally:
        ch.close()
        srv.stop()
    return {
        "values": len(values),
        "hbm_bytes": loaded,
        "load_s": load_s,
        "read_back": len(sample),
        "odd_reads": len(odd),
        "unchecked_segments": unchecked,
    }


def ps_data(dim, rows, seed):
    """Forward inputs of the exact-integer kind (see PS_INT_RANGE)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    k = PS_INT_RANGE
    W = rng.integers(-k, k + 1, (dim, dim), dtype=np.int8)
    W = W.astype(np.float32) * np.float32(PS_W_SCALE)
    X = rng.integers(-k, k + 1, (rows, dim), dtype=np.int8).astype(np.float32)
    return X, W


def _forward_batch(port, X):
    """Every row of X as one concurrent Forward over TCP, so the
    server's batcher coalesces them; returns the (rows, d) results."""
    import threading

    import numpy as np

    from incubator_brpc_tpu.client.channel import Channel, ChannelOptions
    from incubator_brpc_tpu.client.controller import Controller
    from incubator_brpc_tpu.models.parameter_server import ps_stub
    from incubator_brpc_tpu.protos.echo_pb2 import EchoRequest

    ch = Channel(ChannelOptions(timeout_ms=120000))
    assert ch.init(f"127.0.0.1:{port}") == 0
    stub = ps_stub(ch)
    left = [len(X)]
    lock, done_all = threading.Lock(), threading.Event()
    ctrls = []

    def on_done():
        with lock:
            left[0] -= 1
            if left[0] == 0:
                done_all.set()

    try:
        for row in X:
            c = Controller()
            c.timeout_ms = 120000
            c.request_attachment.append(row.tobytes())
            ctrls.append(c)
            stub.Forward(c, EchoRequest(message="w"), done=on_done)
        assert done_all.wait(300), "Forward batch did not complete"
    finally:
        ch.close()
    for c in ctrls:
        assert not c.failed(), c.error_text()
    return np.stack([
        np.frombuffer(c.response_attachment.to_bytes(), np.float32)
        for c in ctrls
    ])


def _ps_serve(svc, W, X):
    """Serve W from ``svc`` on a batching TCP server; Forward X."""
    from incubator_brpc_tpu.server.server import Server, ServerOptions

    srv = Server(ServerOptions(enable_batching=True))
    srv.add_service(svc)
    assert srv.start(0) == 0
    try:
        sharded = svc.put_param("w", W)
        batcher = srv.batcher("PsService.Forward")
        kern = svc.shard_kernel
        b0 = batcher.batches
        e0, m0 = (kern.executions, kern.collective_merges) if kern else (0, 0)
        Y = _forward_batch(srv.port, X)
        counts = {
            "sharded": bool(sharded),
            "batches": batcher.batches - b0,
            "executions": (kern.executions - e0) if kern else None,
            "collective_merges": (kern.collective_merges - m0) if kern else None,
        }
    finally:
        srv.stop()
    return Y, counts


def _reference(X, W):
    import numpy as np

    return (X.astype(np.float64) @ W.astype(np.float64)).astype(np.float32)


def phase_ps_forward(device, dim=PS_DIM, rows=PS_ROWS, seed=2):
    """One-chip PsService: W resident on ``device``, a batched Forward
    over RPC, compared exactly with NumPy float64.  Returns (Y, info)."""
    import jax
    import numpy as np

    from incubator_brpc_tpu.models.parameter_server import PsService

    X, W = ps_data(dim, rows, seed)
    svc = PsService()
    Y, counts = _ps_serve(svc, jax.device_put(W, device), X)
    assert not counts["sharded"]
    ref = _reference(X, W)
    assert Y.shape == ref.shape, Y.shape
    assert np.array_equal(Y, ref), (
        f"Forward differs from X @ W: max |diff| {np.abs(Y - ref).max()}"
    )
    return Y, {"dim": dim, "rows": rows, "w_bytes": int(W.nbytes), **counts}


def phase_four_chips(devices, shape=ECHO_SHAPE, dim=PS_DIM, rows=PS_ROWS,
                     seed=2, one_chip_y=None):
    """Four one-chip servers in this process, a chip 0 -> chip 3 echo,
    and the sharded PsService against the one-chip result and NumPy."""
    import numpy as np

    from incubator_brpc_tpu.client.channel import Channel, ChannelOptions
    from incubator_brpc_tpu.models.echo import EchoService, echo_stub
    from incubator_brpc_tpu.models.parameter_server import PsService
    from incubator_brpc_tpu.parallel.ici import get_fabric
    from incubator_brpc_tpu.parallel.mesh import create_mesh
    from incubator_brpc_tpu.server.server import Server, ServerOptions

    assert len(devices) == 4, devices
    fabric = get_fabric()
    servers = []
    out = {}
    try:
        for k in range(4):
            srv = Server(ServerOptions(usercode_in_dispatcher=True))
            srv.add_service(EchoService())
            assert srv.start_ici(0, k) == 0
            servers.append(srv)
        placed = [fabric.port((0, k)).device for k in range(4)]
        assert placed == list(devices), f"servers landed on {placed}"
        out["server_devices"] = [str(d) for d in placed]
        x = _random_on(devices[0], shape, seed)
        ch = Channel(ChannelOptions(timeout_ms=120000, ici_device=devices[0]))
        try:
            assert ch.init("ici://slice0/chip3") == 0
            _check_echo(_echo(echo_stub(ch), x), x, devices[0])
        finally:
            ch.close()
        out["cross_chip_echo_bytes"] = int(x.nbytes)
    finally:
        for srv in servers:
            srv.stop()

    X, W = ps_data(dim, rows, seed)
    svc = PsService(mesh=create_mesh((1, 4), devices=devices))
    Y, counts = _ps_serve(svc, W, X)
    assert counts["sharded"], "W did not shard over the mesh"
    assert counts["executions"] == counts["batches"], counts
    assert counts["collective_merges"] == counts["batches"], counts
    ref = _reference(X, W)
    assert np.array_equal(Y, ref), (
        f"sharded Forward differs from X @ W: max |diff| "
        f"{np.abs(Y - ref).max()}"
    )
    if one_chip_y is not None:
        assert np.array_equal(Y, one_chip_y), "sharded != one-chip Forward"
    out.update(counts)
    return out


def _run(name, clock, fn, *args, **kwargs):
    s0, n0 = clock.snapshot()
    t0 = time.perf_counter()
    result = fn(*args, **kwargs)
    s1, n1 = clock.snapshot()
    line = {"phase": name, "ok": True,
            "seconds": time.perf_counter() - t0,
            "compile_s": s1 - s0, "compiles": n1 - n0}
    return result, line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (first device is {devices[0].platform})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              f"device(s)", file=sys.stderr)
        return 2

    from incubator_brpc_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    clock = _CompileClock()
    dev = devices[0]

    def emit(line, **extra):
        print(json.dumps({**line, **extra}), flush=True)

    if args.chips == 4:
        y1, line = _run("ps_forward", clock, phase_ps_forward, dev)
        emit(line, **y1[1])
        out, line = _run("four_chips", clock, phase_four_chips,
                         devices[:4], one_chip_y=y1[0])
        emit(line, **out)
    else:
        def ici_phase():
            out = phase_ici_echo(dev)
            out["kernel_checksums"] = transmit_kernel_checksums(
                _random_on(dev, ECHO_SHAPE, 0)
            )
            out["batch_program_segments"] = batch_transmit_checksums(dev)
            return out

        out, line = _run("ici_echo", clock, ici_phase)
        # both hops of every echo, bulk and 4 KiB, ride a checksumming
        # kernel: none took the XLA-copy lane
        assert out["unchecked_segments"] == 0, out
        emit(line, cache_dir=cache_dir, **out)

        out, line = _run("hbm_cache", clock, phase_hbm_cache, dev)
        # a GET reply is the store's row slice, handed off: no hop copies
        # it, on the kernel lane or the XLA-copy lane
        assert out["unchecked_segments"] == 0, out
        emit(line, **out)

        (_, out), line = _run("ps_forward", clock, phase_ps_forward, dev)
        emit(line, **out)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
