"""The one child process that owns the chip, and how the benchmark talks to
it. Copied from chip_smoke.py's `Server` (the original stays the smoke's);
this process never initialises a JAX backend."""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

from benchmarks.lib.spec import ROOT

GRPC_OPTIONS = [("grpc.max_receive_message_length", 256 << 20),
                ("grpc.max_send_message_length", 256 << 20)]
SERVICE = "/weaviatetpu.v1.Weaviate/"


class ServerFailed(Exception):
    pass


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def http(method: str, url: str, body=None, timeout: float = 30.0):
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(url, data=data, method=method)
    req.add_header("Content-Type", "application/json")
    with urllib.request.urlopen(req, timeout=max(timeout, 0.1)) as r:
        raw = r.read()
    if not raw:
        return None
    try:
        return json.loads(raw)
    except ValueError:
        return raw.decode("utf-8", "replace")


def child_env(extra: dict | None = None) -> dict:
    """The environment of every child: the caller's, with the checkout on
    the path. The compile cache is the program's own business
    (weaviate_tpu/device.py): `JAX_COMPILATION_CACHE_DIR` where the machine
    sets it, else `<checkout>/.jax_cache`, a fixed path inside the checkout;
    /v1/meta says which, and the benchmark counts its files."""
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra or {})
    return env


class Server:
    def __init__(self, data_path: str, log_path: str, env: dict | None = None):
        self.port, self.grpc_port, self.metrics_port = (
            free_port(), free_port(), free_port())
        self.base = f"http://127.0.0.1:{self.port}"
        self.log_path = log_path
        extra = {"PROMETHEUS_MONITORING_ENABLED": "true",
                 "PROMETHEUS_MONITORING_PORT": str(self.metrics_port)}
        extra.update(env or {})
        self._log = open(log_path, "wb")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "weaviate_tpu", "--host", "127.0.0.1",
             "--port", str(self.port), "--grpc-port", str(self.grpc_port),
             "--data-path", data_path],
            env=child_env(extra), cwd=ROOT, stdout=self._log,
            stderr=subprocess.STDOUT)

    def log_text(self) -> str:
        self._log.flush()
        with open(self.log_path, "rb") as f:
            return f.read().decode("utf-8", "replace")

    def wait_ready(self, deadline: float) -> None:
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise ServerFailed(
                    f"server exited rc={self.proc.returncode} before it was "
                    f"ready:\n{self.log_text()[-3000:]}")
            try:
                http("GET", self.base + "/v1/.well-known/ready", timeout=2)
                return
            except (OSError, urllib.error.URLError):
                time.sleep(0.25)
        raise ServerFailed("server never answered /v1/.well-known/ready")

    def get(self, path: str, timeout: float = 30.0):
        return http("GET", self.base + path, timeout=timeout)

    def metrics_text(self, timeout: float = 30.0) -> str:
        return http("GET", f"http://127.0.0.1:{self.metrics_port}/metrics",
                    timeout=timeout)

    def channel(self):
        import grpc

        return grpc.insecure_channel(f"127.0.0.1:{self.grpc_port}",
                                     options=GRPC_OPTIONS)

    def stop(self, timeout: float) -> int:
        """SIGTERM, then wait; a child that outlives the limit is killed."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=max(timeout, 1.0))
            except subprocess.TimeoutExpired:
                self.kill()
                raise ServerFailed(
                    f"server ignored SIGTERM for {timeout:.0f}s; killed")
        return self.proc.returncode

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait(timeout=30)
        if not self._log.closed:
            self._log.close()


def stubs(channel):
    """(Search, BatchSearch) multicallables on `channel`. Built here, not
    taken from server/grpc_server.SearchClient: importing the server imports
    jax, and this process stays off it."""
    from weaviate_tpu.grpcapi import weaviate_pb2 as pb

    return (
        channel.unary_unary(
            SERVICE + "Search",
            request_serializer=pb.SearchRequest.SerializeToString,
            response_deserializer=pb.SearchReply.FromString),
        channel.unary_unary(
            SERVICE + "BatchSearch",
            request_serializer=pb.BatchSearchRequest.SerializeToString,
            response_deserializer=pb.BatchSearchReply.FromString),
    )


def prom_samples(text: str) -> list[tuple[str, dict, float]]:
    from prometheus_client.parser import text_string_to_metric_families

    return [(s.name, dict(s.labels), float(s.value))
            for family in text_string_to_metric_families(text)
            for s in family.samples]


def count_files(path) -> int:
    if not path or not os.path.isdir(path):
        return 0
    return sum(len(files) for _, _, files in os.walk(path))


def dir_bytes(path: str) -> int:
    total = 0
    for d, _, files in os.walk(path):
        for f in files:
            try:
                total += os.path.getsize(os.path.join(d, f))
            except OSError:
                pass
    return total
