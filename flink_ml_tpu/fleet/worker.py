"""Replica worker — the process half of :class:`ProcessReplica`.

Entry point (``python -m flink_ml_tpu.fleet.worker``): builds one
``InferenceServer`` with its own flight-recorder journal (under
``<workdir>/journal``), an ephemeral /healthz + /metrics endpoint, and —
through the inherited ``FLINK_ML_TPU_PLANCACHE_DIR`` — the fleet's shared
plan cache, so a respawned replica warms from serialized executables with
zero serving-path compiles (docs/plancache.md).

Protocol: a ``multiprocessing.connection.Listener`` on an ephemeral
localhost port (authkey from ``FLINK_ML_TPU_FLEET_AUTHKEY``); the parent
opens one connection per outstanding request and the worker answers each
with exactly one reply. Once the server is warmed and listening, the worker
atomically publishes ``<workdir>/ready.json`` (pid, address, telemetry
port) — the parent's spawn barrier. Ops: ``predict``, ``swap``,
``rollback``, ``rollback_bad`` (RollbackController — the canary quarantine
path), ``health``, ``stats``, ``close``.

An abandoned connection (the parent hedged the request elsewhere, or died)
only ends that connection's thread; the serving loop is untouched.
"""
from __future__ import annotations

import argparse
import json
import os
import pickle
import threading
from multiprocessing.connection import Listener
from typing import Any, Dict, Optional

from flink_ml_tpu.fleet.replica import AUTHKEY_ENV, decode_df, encode_df, encode_error

__all__ = ["main"]


class _Worker:
    def __init__(self, args):
        import flink_ml_tpu.telemetry as telemetry
        from flink_ml_tpu.metrics import MLMetrics, metrics
        from flink_ml_tpu.serving.server import InferenceServer, ServingConfig

        self._telemetry = telemetry
        self._metrics = metrics
        self._plancache_group = MLMetrics.PLANCACHE_GROUP
        self.args = args
        self.workdir = args.workdir
        os.makedirs(self.workdir, exist_ok=True)
        telemetry.configure(os.path.join(self.workdir, "journal"))
        template = None
        if args.template:
            with open(args.template, "rb") as f:
                template = decode_df(pickle.load(f))
        self.server = InferenceServer(
            name=args.name,
            serving_config=ServingConfig(http_port=0),
            warmup_template=template,
        )
        if args.publish_dir and args.load_version is not None:
            from flink_ml_tpu.serving.registry import VERSION_PREFIX
            from flink_ml_tpu.servable.api import load_servable

            path = os.path.join(args.publish_dir, f"{VERSION_PREFIX}{args.load_version}")
            self.server.swap(int(args.load_version), load_servable(path))
        self._stop = threading.Event()
        telemetry.emit(
            "fleet.replica.up",
            self.server.scope,
            {
                "name": args.name,
                "pid": os.getpid(),
                "version": self.server.model_version,
            },
        )

    # -- one RPC --------------------------------------------------------------
    def handle(self, msg: Dict[str, Any]) -> Dict[str, Any]:
        op = msg.get("op")
        if op == "predict":
            resp = self.server.predict(
                decode_df(msg["df"]),
                timeout_ms=msg.get("timeout_ms"),
                priority=int(msg.get("priority") or 0),
            )
            return {
                "ok": True,
                "df": encode_df(resp.dataframe),
                "model_version": resp.model_version,
                "latency_ms": resp.latency_ms,
                "bucket": resp.bucket,
            }
        if op == "swap":
            from flink_ml_tpu.servable.api import load_servable

            self.server.swap(int(msg["version"]), load_servable(msg["path"]))
            return {"ok": True, "version": int(msg["version"])}
        if op == "rollback":
            from flink_ml_tpu.servable.api import load_servable

            self.server.rollback(int(msg["version"]), load_servable(msg["path"]))
            return {"ok": True, "version": int(msg["version"])}
        if op == "rollback_bad":
            from flink_ml_tpu.loop.rollback import RollbackController
            from flink_ml_tpu.metrics import MLMetrics

            if not self.args.publish_dir:
                raise RuntimeError("worker has no --publish-dir; cannot rollback_bad")
            controller = RollbackController(
                self.server,
                self.args.publish_dir,
                scope=f"{MLMetrics.FLEET_GROUP}[{self.args.name}]",
            )
            return {"ok": True, "restored": controller.rollback(int(msg["version"]))}
        if op == "health":
            ok, payload = self.server.health()
            return {"ok": True, "healthy": ok, "payload": payload}
        if op == "stats":
            serving = self._metrics.scope(self.server.scope)
            plancache = self._metrics.scope(self._plancache_group)
            numeric = lambda d: {  # noqa: E731
                k: v for k, v in d.items() if isinstance(v, (int, float))
            }
            return {
                "ok": True,
                "stats": {"serving": numeric(serving), "plancache": numeric(plancache)},
            }
        if op == "close":
            self._stop.set()
            return {"ok": True}
        raise ValueError(f"unknown fleet worker op {op!r}")

    def serve_connection(self, conn) -> None:
        try:
            while True:
                try:
                    msg = conn.recv()
                except (EOFError, OSError):
                    return
                try:
                    reply = self.handle(msg)
                except BaseException as e:  # noqa: BLE001 — typed on the wire
                    reply = {"ok": False, "error": encode_error(e)}
                try:
                    conn.send(reply)
                except (BrokenPipeError, OSError):
                    return  # parent hedged elsewhere or died; drop the reply
        finally:
            try:
                conn.close()
            except OSError:
                pass

    # -- lifecycle ------------------------------------------------------------
    def run(self) -> int:
        authkey = bytes.fromhex(os.environ[AUTHKEY_ENV])
        listener = Listener(("127.0.0.1", 0), authkey=authkey)
        ready = {
            "pid": os.getpid(),
            "address": list(listener.address),
            "telemetry_port": self.server.telemetry.port,
            "scope": self.server.scope,
            "name": self.args.name,
            "version": self.server.model_version,
        }
        ready_path = os.path.join(self.workdir, "ready.json")
        tmp_path = ready_path + ".tmp"
        with open(tmp_path, "w", encoding="utf-8") as f:
            json.dump(ready, f)
        os.rename(tmp_path, ready_path)  # atomic: existence implies complete

        def closer() -> None:
            self._stop.wait()
            try:
                listener.close()  # unblocks accept()
            except OSError:
                pass

        threading.Thread(target=closer, daemon=True, name="fleet-worker-closer").start()
        while not self._stop.is_set():
            try:
                conn = listener.accept()
            except (OSError, EOFError):
                break
            threading.Thread(
                target=self.serve_connection, args=(conn,), daemon=True,
                name="fleet-worker-conn",
            ).start()
        self.server.close(drain=True)
        self._telemetry.emit(
            "fleet.replica.down", self.server.scope, {"name": self.args.name}
        )
        self._telemetry.get_recorder().close()
        return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description="fleet replica worker")
    parser.add_argument("--name", required=True)
    parser.add_argument("--workdir", required=True)
    parser.add_argument("--publish-dir", default=None)
    parser.add_argument("--load-version", type=int, default=None)
    parser.add_argument("--template", default=None)
    args = parser.parse_args(argv)
    from flink_ml_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    return _Worker(args).run()


if __name__ == "__main__":
    raise SystemExit(main())
