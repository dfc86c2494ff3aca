"""Dedicated aggregator service.

Role parity with the reference m3aggregator assembly: consumes metrics over
the msg transport, aggregates with the rule-matched elem grid, and flushes
aggregated output to a downstream producer — with leader/follower flush
control via the KV election (followers shadow-aggregate and only emit after
taking leadership, the election_mgr/follower_flush_mgr roles).

Run: python -m m3_tpu.services.aggregator -f config/aggregator.yml
"""

from __future__ import annotations

import argparse
import json
import threading
import time

from m3_tpu.aggregator.engine import Aggregator
from m3_tpu.cluster.kv import KVStore
from m3_tpu.cluster.services import LeaderService
from m3_tpu.metrics.aggregation import MetricType
from m3_tpu.msg.consumer import Consumer
from m3_tpu.msg.producer import Producer
from m3_tpu.services.coordinator import ruleset_from_config
from m3_tpu.utils import backend
from m3_tpu.utils.config import load_config
from m3_tpu.utils.instrument import Logger, default_registry


def encode_metric(metric_type: int, series_id: bytes, tags, t_ns: int,
                  value: float) -> bytes:
    """Wire payload for aggregator ingest over msg."""
    return json.dumps(
        {
            "type": metric_type,
            "id": series_id.hex(),
            "tags": [[k.hex(), v.hex()] for k, v in tags],
            "t": t_ns,
            "v": value,
        }
    ).encode()


def decode_metric(payload: bytes):
    doc = json.loads(payload)
    return (
        MetricType(doc["type"]),
        bytes.fromhex(doc["id"]),
        [(bytes.fromhex(k), bytes.fromhex(v)) for k, v in doc["tags"]],
        doc["t"],
        doc["v"],
    )


class AggregatorService:
    def __init__(self, config: dict, kv: KVStore | None = None):
        self.config = config
        self.log = Logger("aggregator")
        self.instance_id = config.get("instance_id", "agg-0")
        self.aggregator = Aggregator(
            ruleset_from_config(config.get("rules")),
            n_shards=config.get("n_shards", 4),
            buffer_past_ns=int(config.get("buffer_past_s", 5)) * 10**9,
        )
        kv_cfg = config.get("kv", {}) or {}
        if kv is not None:
            self.kv = kv
        else:
            from m3_tpu.cluster.kv import kv_from_config

            self.kv = kv_from_config(kv_cfg, addr_key="addr", path_key="path") \
                or KVStore()
        self.election = LeaderService(
            self.kv, config.get("election_id", "m3agg"), self.instance_id,
            lease_ttl_s=float(config.get("lease_ttl_s", 10.0)),
        )
        self.consumer: Consumer | None = None
        self.producer: Producer | None = None
        out = config.get("output", {}) or {}
        if "host" in out:
            self.producer = Producer((out["host"], int(out["port"])))
        self._stop = threading.Event()
        self.scope = default_registry().root_scope(
            "aggregator").subscope("svc", instance=self.instance_id)
        # OTLP-style telemetry export (config `export:` / M3_TPU_EXPORT_*
        # env): the aggregator's ingest/flush counters and msg-seam
        # histograms drain to the same collector as the other services
        from m3_tpu.utils.export import exporter_from_config

        self.exporter = exporter_from_config(config, "aggregator")
        if self.exporter is not None:
            self.exporter.start()
        # always-on profiling plane. The aggregator has no HTTP API of
        # its own, so `debug_port:` (or M3_TPU_DEBUG_PORT) starts the
        # shared debug surface serving /debug/profile + /metrics.
        from m3_tpu.utils import profiler

        profiler.arm_from_env("aggregator")
        debug_port = config.get("debug_port")
        if debug_port is not None:
            self.debug_server = profiler.DebugServer(port=int(debug_port))
        else:
            self.debug_server = profiler.serve_debug_from_env()

    def _on_message(self, shard: int, payload: bytes) -> None:
        mt, sid, tags, t_ns, value = decode_metric(payload)
        self.aggregator.add(mt, sid, tags, t_ns, value)
        self.scope.counter("ingested")

    def flush_once(self, now_ns: int | None = None) -> int:
        """Campaign; leaders emit, followers shadow-aggregate only
        (their buffered windows carry until promotion)."""
        now_ns = now_ns if now_ns is not None else time.time_ns()
        if not self.election.campaign(now_ns):
            self.scope.counter("follower_skips")
            return 0
        metrics = self.aggregator.flush(now_ns)
        for m in metrics:
            if self.producer is not None:
                self.producer.publish(
                    0,
                    encode_metric(
                        MetricType.GAUGE, m.series_id, list(m.tags),
                        m.timestamp_ns, m.value,
                    ),
                )
        self.scope.counter("flushed", len(metrics))
        return len(metrics)

    def run(self) -> None:
        backend.init(self.log)  # once, before anything listens
        ingest = self.config.get("ingest", {}) or {}
        self.consumer = Consumer(
            self._on_message,
            host=ingest.get("host", "0.0.0.0"),
            port=int(ingest.get("port", 7206)),
        )
        self.log.info("ingest listening", port=self.consumer.port)
        flush_every = float(self.config.get("flush_interval_s", 5.0))
        from m3_tpu.utils import profiler

        hb = profiler.register_heartbeat("aggregator.flush", flush_every)
        try:
            while not self._stop.is_set():
                self._stop.wait(flush_every)
                if self._stop.is_set():
                    break
                hb.beat()
                try:
                    self.flush_once()
                except Exception as e:  # noqa: BLE001 - one bad flush must
                    # not kill the service loop. A SimulatedCrash is the
                    # exception to that: armed (chaos rig,
                    # M3_TPU_FAULTS_EXIT=1) the whole process dies here;
                    # unarmed it propagates — no handler survives a
                    # SIGKILL, in-process chaos tests included
                    from m3_tpu.utils import faults

                    if isinstance(e, faults.SimulatedCrash):
                        faults.escalate(e)
                        raise
                    self.log.info("flush error; continuing", error=str(e))
        finally:
            self.shutdown()

    def shutdown(self) -> None:
        self._stop.set()
        from m3_tpu.utils import profiler

        profiler.default_watchdog().unregister("aggregator.flush")
        if self.consumer:
            self.consumer.close()
        if self.producer:
            self.producer.close()
        if self.exporter is not None:
            self.exporter.close()  # final best-effort flush
        if self.debug_server is not None:
            self.debug_server.close()
        self.election.resign()
        self.log.info("aggregator stopped")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("-f", "--config", required=True)
    args = ap.parse_args(argv)
    svc = AggregatorService(load_config(args.config) or {})
    try:
        svc.run()
    except KeyboardInterrupt:
        svc.shutdown()


if __name__ == "__main__":
    main()
