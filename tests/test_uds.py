"""Unix-domain-socket transport for the gateway→backend hop.

The co-located deployment (`gateway --tpu`, serving/launcher.py) rides a
private UDS by default: the hop never leaves the host, and a UDS round
trip costs less shared-core CPU than TCP loopback. These tests pin that the whole RPC stack — dial,
reflection discovery, invocation, health — is transport-agnostic, and
that the sidecar/launcher wiring produces working unix targets.
"""

import os
import tempfile

import pytest

from ggrmcp_tpu.core.config import GRPCConfig, default as default_config
from ggrmcp_tpu.rpc.discovery import ServiceDiscoverer
from tests.backend_utils import InProcessBackend


def _sock_path(name: str) -> str:
    return os.path.join(tempfile.gettempdir(), f"ggrmcp-test-{name}-{os.getpid()}.sock")


class TestUDSTransport:
    async def test_discover_and_invoke_over_uds(self):
        path = _sock_path("rpc")
        try:
            async with InProcessBackend(uds=path) as backend:
                assert backend.target == f"unix:{path}"
                d = ServiceDiscoverer(
                    backend.target, GRPCConfig(connect_timeout_s=5.0)
                )
                await d.connect()
                try:
                    await d.discover_services()
                    tools = {m.tool_name for m in d.get_methods()}
                    assert "hello_helloservice_sayhello" in tools
                    result = await d.invoke_by_tool(
                        "hello_helloservice_sayhello", {"name": "uds"}
                    )
                    assert result["message"] == "Hello, uds!"
                finally:
                    await d.close()
        finally:
            if os.path.exists(path):
                os.unlink(path)

    @pytest.mark.slow
    async def test_sidecar_binds_uds(self):
        """Sidecar with serving.uds_path listens on the socket only and
        reports a dialable unix target; stop() removes the socket file."""
        from ggrmcp_tpu.core.config import BatchingConfig, MeshConfig
        from ggrmcp_tpu.serving.sidecar import Sidecar

        cfg = default_config()
        cfg.serving.model = "tiny-llama"
        cfg.serving.mesh = MeshConfig(tensor=2, data=0)
        cfg.serving.batching = BatchingConfig(
            max_batch_size=4, kv_cache_max_seq=256
        )
        cfg.serving.uds_path = _sock_path("sidecar")
        sidecar = Sidecar(cfg.serving)
        port = await sidecar.start()
        try:
            assert port == 0
            assert sidecar.target == f"unix:{cfg.serving.uds_path}"
            assert os.path.exists(cfg.serving.uds_path)
            d = ServiceDiscoverer(
                sidecar.target, GRPCConfig(connect_timeout_s=10.0)
            )
            await d.connect()
            try:
                await d.discover_services()
                tools = {m.tool_name for m in d.get_methods()}
                assert any("generate" in t for t in tools)
            finally:
                await d.close()
        finally:
            await sidecar.stop()
        assert not os.path.exists(cfg.serving.uds_path)


class TestConfigValidation:
    def test_uds_path_length_rejected(self):
        cfg = default_config()
        cfg.serving.uds_path = "/tmp/" + "x" * 120
        with pytest.raises(ValueError, match="uds_path"):
            cfg.validate()

    def test_uds_path_ok(self):
        cfg = default_config()
        cfg.serving.uds_path = "/tmp/ggrmcp.sock"
        cfg.validate()
