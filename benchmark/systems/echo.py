"""Echo over ICI: brpc's EchoService with a device attachment.

The configuration names the client's chip, the servers' chips, and the
``ServerOptions`` / ``ChannelOptions`` it runs with; the traffic names
the attachment's shape and dtype, the pool of payloads each caller
cycles through, and the callers bound to each server.  Each caller has
a channel of its own.

Guarantees checked (``check``): every sampled response is bit-equal to
its request, lies on the client's chip, and every request reached its
server on the server's chip (so a hop that skipped the exchange between
chips is seen).
"""

from __future__ import annotations

import threading

import numpy as np

from generator import Reservoir
from seeds import key32, rng


def make_payloads(seed, shape, dtype, count, device):
    """``count`` payloads made on ``device`` from the seed, in one
    jitted call."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    shape, dtype = tuple(shape), jnp.dtype(dtype)

    def gen(key):
        return tuple(jax.random.normal(k, shape, dtype)
                     for k in jax.random.split(key, count))

    fn = jax.jit(gen, out_shardings=SingleDeviceSharding(device))
    out = fn(jax.random.key(key32(seed, 1)))
    jax.block_until_ready(out)
    return list(out)


def _bits(a):
    import jax
    import jax.numpy as jnp

    width = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}
    return jax.lax.bitcast_convert_type(a, width[a.dtype.itemsize])


def mismatched_elems(got, want) -> int:
    """Elements whose bits differ (the reference of an echo is its
    request, bit for bit)."""
    import jax
    import jax.numpy as jnp

    if got.shape != want.shape or got.dtype != want.dtype:
        return int(np.prod(want.shape))
    got = jax.device_put(got, list(want.devices())[0])
    return int(jnp.sum(_bits(got) != _bits(want)))


def _service_class():
    from incubator_brpc_tpu.models.echo import EchoService

    class PlacementCheckedEcho(EchoService):
        """EchoService, noting any request that reached it off its chip."""

        SERVICE_NAME = "EchoService"

        def __init__(self, device):
            super().__init__()
            self.device = device
            self.misplaced = 0
            self._lock = threading.Lock()

        def Echo(self, controller, request, response, done):
            for a in controller.request_attachment.device_arrays():
                if a.devices() != {self.device}:
                    with self._lock:
                        self.misplaced += 1
            super().Echo(controller, request, response, done)

    return PlacementCheckedEcho


class _Caller:
    def __init__(self, stub, pool, first, sample):
        from incubator_brpc_tpu.client.controller import Controller
        from incubator_brpc_tpu.protos.echo_pb2 import EchoRequest

        self._Controller = Controller
        self._req = EchoRequest(message="bench")
        self.stub = stub
        self.pool = pool
        self.first = first
        self.sample = sample

    def call(self, k: int) -> bool:
        i = (self.first + k) % len(self.pool)
        c = self._Controller()
        c.request_attachment.append_device(self.pool[i])
        self.stub.Echo(c, self._req)
        if c.failed():
            return False
        arrs = c.response_attachment.device_arrays()
        for a in arrs:
            a.block_until_ready()
        self.sample.offer(lambda: (i, arrs))
        return True


class _ControlCaller(_Caller):
    """The reference in the program's place, one precision down: the
    echo rounded to bfloat16 (float32 payloads) on the client's chip.
    ``reduce_precision`` and not a pair of casts: XLA may drop a cast
    pair under its default excess-precision rule, and did on the v5e."""

    def __init__(self, pool, first, sample):
        import jax

        self.pool, self.first, self.sample = pool, first, sample
        self._fn = jax.jit(lambda x: jax.lax.reduce_precision(
            x, exponent_bits=8, mantissa_bits=7))

    def call(self, k: int) -> bool:
        i = (self.first + k) % len(self.pool)
        out = self._fn(self.pool[i])
        out.block_until_ready()
        self.sample.offer(lambda: (i, [out]))
        return True


class EchoBench:
    def __init__(self, cell, devices, seed, control=False):
        from incubator_brpc_tpu.client.channel import Channel, ChannelOptions
        from incubator_brpc_tpu.models.echo import echo_stub
        from incubator_brpc_tpu.server.server import Server, ServerOptions

        cfg, tr = cell.config, cell.traffic
        self.client_dev = devices[cfg["client_chip"]]
        self.chips = sorted({cfg["client_chip"], *cfg["server_chips"]})
        self.pool = make_payloads(seed, tr["shape"], tr["dtype"],
                                  tr["pool"], self.client_dev)
        nbytes = int(self.pool[0].nbytes)
        self.frame_bytes = nbytes
        self.bytes_per_request = 2 * nbytes  # request + response
        self.hops_per_request = 2
        self.servers, self.services, self.channels, self.callers = [], [], [], []
        ncallers = tr["callers_per_server"] * len(cfg["server_chips"])
        per = max(1, tr["sample"] // ncallers)
        Service = _service_class()
        for chip in cfg["server_chips"]:
            dev = devices[chip]
            if not control:
                svc = Service(dev)
                srv = Server(ServerOptions(**cfg.get("server_options", {})))
                srv.add_service(svc)
                if srv.start_ici(0, chip, device=dev) != 0:
                    raise RuntimeError(f"start_ici on chip {chip} failed")
                self.servers.append(srv)
                self.services.append(svc)
            for _ in range(tr["callers_per_server"]):
                n = len(self.callers)
                sample = Reservoir(per, rng(seed, 2, n))
                if control:
                    self.callers.append(_ControlCaller(self.pool, n, sample))
                    continue
                opts = dict(cfg.get("channel_options", {}))
                opts["ici_device"] = self.client_dev
                ch = Channel(ChannelOptions(**opts))
                if ch.init(f"ici://slice0/chip{chip}") != 0:
                    raise RuntimeError(f"channel to chip {chip} failed")
                self.channels.append(ch)
                self.callers.append(_Caller(echo_stub(ch), self.pool, n,
                                            sample))

    def counters(self) -> dict:
        from incubator_brpc_tpu.parallel import ici

        return {
            "rpc_ici_pallas_frames": int(ici.ici_pallas_frames.get_value()),
            "rpc_ici_pallas_fallbacks": int(ici.ici_pallas_fallbacks.get_value()),
            "rpc_ici_unchecked_segments": int(ici.ici_unchecked_segments.get_value()),
        }

    def close(self):
        for ch in self.channels:
            ch.close()
        for srv in self.servers:
            srv.stop()
        self.channels, self.servers = [], []

    def check(self, failed: int) -> dict:
        """The numbers compared, each with its limit.  ``wrong_answers``
        counts every way an answer can be wrong or missing: a sampled
        reply not bit-equal to its request or off the client's chip, a
        request that reached its server off the server's chip, a
        request that failed."""
        parts = {"not_bit_equal": 0, "off_client_chip": 0,
                 "off_server_chip": sum(s.misplaced for s in self.services),
                 "failed": failed}
        sampled = 0
        for c in self.callers:
            for i, arrs in c.sample.items:
                sampled += 1
                if len(arrs) != 1:
                    parts["not_bit_equal"] += 1
                    continue
                if arrs[0].devices() != {self.client_dev}:
                    parts["off_client_chip"] += 1
                if mismatched_elems(arrs[0], self.pool[i]):
                    parts["not_bit_equal"] += 1
        return {
            "parts": parts,
            "sampled_replies": {"value": sampled, "limit": 1, "at_least": True},
            "wrong_answers": {"value": sum(parts.values()), "limit": 0},
        }


def build(cell, devices, seed, control=False):
    return EchoBench(cell, devices, seed, control=control)
