"""Kitchen-sink composition: every serving feature enabled at once —
int8 weights + int8 KV cache + length-tiered pools + paged prefix
sharing — on one sidecar, driven over real gRPC. Guards
against feature-interaction regressions that per-feature suites miss.
"""

import asyncio

import grpc
import grpc.aio

from ggrmcp_tpu.core.config import (
    BatchingConfig,
    MeshConfig,
    ServingConfig,
)
from ggrmcp_tpu.core import config as cfgmod
from ggrmcp_tpu.rpc.pb import serving_pb2
from ggrmcp_tpu.serving.sidecar import Sidecar


def maximal_serving() -> ServingConfig:
    return ServingConfig(
        model="tiny-llama",
        quantize="int8",
        kv_cache_dtype="int8",
        mesh=MeshConfig(tensor=2, data=0),
        batching=BatchingConfig(
            max_batch_size=4,
            kv_cache_max_seq=256,
            kv_tiers=[[64, 3], [256, 2]],
            prefill_chunk=32,
            paged_kv="on",
            paged_kv_page_size=8,
        ),
    )


def test_maximal_config_validates():
    cfg = cfgmod.default()
    cfg.serving = maximal_serving()
    cfg.validate()  # must not raise


class TestMaximalSidecar:
    async def test_all_features_serve_together(self):
        side = Sidecar(maximal_serving())
        assert type(side.batcher).__name__ == "TieredBatcher"
        port = await side.start(0)
        channel = grpc.aio.insecure_channel(f"localhost:{port}")
        try:
            gen = channel.unary_unary(
                "/ggrmcp.tpu.GenerateService/Generate",
                request_serializer=(
                    serving_pb2.GenerateRequest.SerializeToString
                ),
                response_deserializer=serving_pb2.GenerateResponse.FromString,
            )
            long_prompt = "shared system preamble " * 4  # 11 full pages

            async def call(prompt, temperature):
                return await gen(serving_pb2.GenerateRequest(
                    prompt=prompt, max_new_tokens=5,
                    sampling=serving_pb2.SamplingParams(
                        temperature=temperature, seed=7
                    ),
                ))

            # Every row rides its tier's tick: greedy and
            # sampled in the short tier; the long prompt in the long
            # tier via the chunked path, registering its pages; its
            # repeat reuses them.
            results = await asyncio.gather(
                call("greedy one", 0.0),
                call("greedy two", 0.0),
                call("sampled", 0.9),
                call(long_prompt + "q1", 0.9),
            )
            results.append(await call(long_prompt + "q2", 0.9))
            for resp in results:
                assert resp.finish_reason in ("length", "stop")
                assert resp.completion_tokens <= 5
                assert resp.model_id == "tiny-llama"

            # Determinism sanity within the quantized config: a repeat
            # of the same greedy prompt reproduces its output.
            again = await call("greedy one", 0.0)
            assert again.text == results[0].text

            # ServingStats reflects the tiers' activity.
            stats_rpc = channel.unary_unary(
                "/ggrmcp.tpu.ModelInfoService/GetServingStats",
                request_serializer=(
                    serving_pb2.ServingStatsRequest.SerializeToString
                ),
                response_deserializer=(
                    serving_pb2.ServingStatsResponse.FromString
                ),
            )
            stats = await stats_rpc(serving_pb2.ServingStatsRequest())
            assert stats.total_slots == 5  # 3 + 2 tier slots
            assert stats.kv_cache_bytes > 0
            assert stats.prefix_cache_hits >= 1  # q2 reused q1's head
            assert stats.paged_pages_reused >= 11
        finally:
            await channel.close()
            await side.stop()


# Heavy JAX-compile/serving integration module: excluded from the
# fast `make test` signal; always in `make test-all` / CI.
import pytest  # noqa: E402  (slow-mark only)
pytestmark = pytest.mark.slow
