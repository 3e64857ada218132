"""Process entry point: `python -m weaviate_tpu`.

Reference: cmd/weaviate-server/main.go:30 — load config from the
environment, assemble the whole object graph, serve REST (+ metrics when
enabled) and gRPC until SIGTERM/SIGINT, then shut down cleanly.

Flags mirror the reference's swagger flags where they matter:
    --host (default 0.0.0.0), --port (default 8080; PORT env also honored),
    --grpc-port (default GRPC_PORT env / 50051), --data-path (overrides
    PERSISTENCE_DATA_PATH). Everything else comes from the env-var surface
    (usecases/config/environment.go twin in weaviate_tpu/config).
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import threading


def main(argv=None) -> int:
    # the restart's timeline first (monitoring/perf.py Timeline): `process`
    # runs from the OS's start of the process to here, and every stage from
    # here to the listeners is a `tracing.stage`
    from weaviate_tpu.monitoring import perf, tracing

    timeline = perf.startup_begin()

    ap = argparse.ArgumentParser(prog="weaviate-tpu", description=__doc__)
    ap.add_argument("--host", default=os.environ.get("HOST", "0.0.0.0"))
    ap.add_argument("--port", type=int, default=int(os.environ.get("PORT", "8080")))
    ap.add_argument("--grpc-port", type=int, default=None)
    ap.add_argument("--data-path", default=None)
    args = ap.parse_args(argv)

    # this process owns the device: place the compile cache, then bring the
    # backend up NOW — a server that cannot reach the platform it was
    # started for fails here, before it binds a port, instead of serving on
    # whatever it got
    with tracing.stage("backend"):
        from weaviate_tpu import device

        cache_dir = device.enable_compile_cache()
        perf.compiles.install()
        ident = device.identity()

    # `app`: the server package's imports, the configuration and the whole
    # of App(...), every shard's recovery inside it
    with tracing.stage("app"):
        from weaviate_tpu.config import load_config
        from weaviate_tpu.server import App, RestServer
        from weaviate_tpu.server.grpc_server import GrpcServer
        from weaviate_tpu.version import __version__

        config = load_config()
        app = App(config=config, data_path=args.data_path)
    with tracing.stage("post_startup"):
        app.db.post_startup()

    stop = threading.Event()

    def handle(signum, frame):
        print(f"received signal {signum}, shutting down", flush=True)
        stop.set()

    # handlers BEFORE the listeners come up: a supervisor that signals the
    # moment readiness flips must hit the graceful path, not the default
    # action
    signal.signal(signal.SIGTERM, handle)
    signal.signal(signal.SIGINT, handle)

    with tracing.stage("listen"):
        rest = RestServer(app, host=args.host, port=args.port)
        grpc_port = args.grpc_port if args.grpc_port is not None else config.grpc_port
        grpc_srv = GrpcServer(app, host=args.host, port=grpc_port)
        rest.start()
        grpc_srv.start()
    # the listeners are up: the timeline closes to all but `first_ready`
    # and feeds weaviate_startup_durations_ms
    startup_seconds = timeline.ready(app.metrics)
    parts = [f"REST http://{args.host}:{rest.port}", f"gRPC {args.host}:{grpc_srv.port}"]
    if getattr(rest, "_metrics_httpd", None) is not None:
        parts.append(f"metrics :{rest.metrics_port}")
    if app.cluster_node is not None:
        parts.append(f"clusterapi {app.cluster_node.address}")
    print(f"weaviate-tpu {__version__} on {ident['platform']} "
          f"({ident['count']} x {ident['device_kind']}), compile cache "
          f"{cache_dir}, serving " + ", ".join(parts), flush=True)
    print("startup: " + json.dumps(startup_seconds), flush=True)
    stop.wait()

    # the way down, on the same recorder: no page can be read after exit,
    # so its stages are one line of the log
    down = perf.shutdown_begin()
    with tracing.stage("grpc.stop"):
        grpc_srv.stop()
    with tracing.stage("rest.stop"):
        rest.stop()
    with tracing.stage("app.shutdown"):
        app.shutdown()
    print("shutdown: " + down.line(), flush=True)
    print("shutdown complete", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
