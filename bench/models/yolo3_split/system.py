"""The system under test: ``repro.serve.ServingGateway`` at the
configuration's operating point, built from the benchmark's seeded weights.

The gateway is given a wire meter in the place of a channel: an ideal link
(zero delay, no budget, which is what the gateway does with no channel)
that counts the bytes of every container it is handed, so the benchmark
measures the bits on the wire itself rather than reading them from the
program's own accounting.
"""
from __future__ import annotations

import contextlib
from dataclasses import dataclass

import numpy as np

from repro.obs import hooks
from repro.obs.metrics import MetricsRegistry
from repro.serve import OperatingPoint, ServingGateway


@dataclass(frozen=True)
class Sent:
    bits: int
    t_submit: float
    t_start: float
    t_arrive: float


class WireMeter:
    """Duck-types the gateway's channel: ``transmit_bytes`` only."""

    def __init__(self):
        self.bytes = 0
        self.sends = 0

    def reset(self) -> None:
        self.bytes = self.sends = 0

    def transmit_bytes(self, data: bytes, t_submit: float) -> Sent:
        self.bytes += len(data)
        self.sends += 1
        return Sent(8 * len(data), t_submit, t_submit, t_submit)


@dataclass
class Served:
    logits: np.ndarray          # (n, classes)
    batches: list               # [(requests, padded size)] per micro-batch
    padded: np.ndarray          # (n,) padded size of each request's batch


class System:
    def __init__(self, cfg: dict, weights):
        params, baf, sel = weights
        self.meter = WireMeter()
        op = OperatingPoint(c=cfg["c"], bits=cfg["bits"],
                            backend=cfg["backend"])
        self.gateway = ServingGateway(
            params, {cfg["c"]: (baf, np.asarray(sel))}, default_op=op,
            max_batch=cfg["max_batch"], fused=cfg["fused_restore"],
            channel=self.meter)

    def serve(self, images: np.ndarray) -> Served:
        responses, telemetry = self.gateway.serve(images)
        # one record per request, a micro-batch's records in a row
        recs, batches, i = telemetry.records, [], 0
        while i < len(recs):
            batches.append((recs[i].batch_size, recs[i].padded_size))
            i += recs[i].batch_size
        padded = np.zeros(len(responses), np.int64)
        for r in recs:
            padded[r.req_id] = r.padded_size
        return Served(logits=np.stack([r.logits for r in responses]),
                      batches=batches, padded=padded)


@contextlib.contextmanager
def stage_timers():
    """The program's stage timers, installed for the traced run only;
    yields ``get(stage) -> (seconds summed, calls)``."""
    registry = MetricsRegistry()

    def get(stage: str):
        hs = [m for _, labels, m in registry.collect()
              if labels.get("stage") == stage
              and getattr(m, "kind", "") == "histogram"]
        return (sum(h.total for h in hs), sum(h.count for h in hs))

    with hooks.active(registry):
        yield get
