"""Standalone hello gRPC server — the interop smoke-test backend
(examples/hello-service capability parity: unary SayHello + reflection
+ health, --port flag).

A SYNC `grpc.server` with a small thread pool, not grpc.aio: the
handler is trivial (one string format), so per-call cost is dominated
by gRPC machinery — the sync C-core path costs ~35% less Python time
per call than the asyncio one, which matters because this process
shares one core with the gateway under test in the proxy bench (the Go
reference's equivalent backend is similarly negligible next to its
gateway, examples/hello-service/main.go).

Run:  python examples/hello_server.py --port 50051
Then: python -m ggrmcp_tpu gateway --grpc-port 50051 --http-port 50053
"""

from __future__ import annotations

import argparse
import logging
import os
import sys
from concurrent import futures

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import grpc

from ggrmcp_tpu.rpc.pb import hello_pb2
from ggrmcp_tpu.rpc.server_utils import (
    HealthService,
    MethodDef,
    ReflectionService,
    add_service,
)


def say_hello(request: hello_pb2.HelloRequest, context) -> hello_pb2.HelloResponse:
    salutation = request.salutation or "Hello"
    return hello_pb2.HelloResponse(message=f"{salutation}, {request.name}!")


def serve(port: int, uds: str = "", workers: int = 4) -> None:
    server = grpc.server(futures.ThreadPoolExecutor(max_workers=workers))
    add_service(
        server,
        "hello.HelloService",
        {"SayHello": MethodDef(say_hello, hello_pb2.HelloRequest, hello_pb2.HelloResponse)},
    )
    ReflectionService(["hello.HelloService"]).attach(server, sync=True)
    HealthService().attach(server, sync=True)
    if uds:
        assert server.add_insecure_port(f"unix:{uds}") != 0, f"bind unix:{uds}"
        target = f"unix:{uds}"
    else:
        bound = server.add_insecure_port(f"0.0.0.0:{port}")
        target = f"localhost:{bound}"
    server.start()
    # Machine-readable for harnesses that pass --port 0 / --uds.
    print(f"TARGET={target}", flush=True)
    logging.info("hello-service listening on %s", target)
    server.wait_for_termination()


if __name__ == "__main__":
    logging.basicConfig(level=logging.INFO)
    parser = argparse.ArgumentParser()
    parser.add_argument("--port", type=int, default=50051)
    parser.add_argument(
        "--uds", default="", help="listen on a unix socket instead of TCP"
    )
    parser.add_argument(
        "--workers", type=int, default=4, help="handler thread-pool size"
    )
    args = parser.parse_args()
    serve(args.port, args.uds, args.workers)
