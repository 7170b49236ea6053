"""Model engines: jitted, sharded prefill/decode/embed with shape
bucketing.

The execution core of the serving plane (SURVEY.md §7 stages 4-5):

- Parameters live on the mesh (`NamedSharding` from the model's
  param_specs); every step is a `jax.jit` with donated KV cache, so
  decode is one XLA program per (batch, bucket) shape with no host
  round-trips inside.
- Prefill handles right-padded variable-length batches: positions are
  causal from 0, per-row true lengths gate the KV mask, last-token
  logits are gathered per row, and the cache length is set to the true
  length so decode overwrites pad slots.
- Full-sequence generation is a single fused `lax.scan` over decode
  steps (compile once, stay on device); streaming uses the per-step
  jit and yields tokens as they materialize.
- Shape bucketing (powers of two) bounds the number of compilations.
"""

from __future__ import annotations

import logging
import math
import time
from functools import partial
from typing import Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ggrmcp_tpu.core.config import ServingConfig
from ggrmcp_tpu.models import bert as bert_mod
from ggrmcp_tpu.models import llama as llama_mod
from ggrmcp_tpu.models import moe as moe_mod
from ggrmcp_tpu.models.common import count_params
from ggrmcp_tpu.ops import quant
from ggrmcp_tpu.ops.sampling import SamplingConfig, sample
from ggrmcp_tpu.parallel import mesh as mesh_mod

logger = logging.getLogger("ggrmcp.serving.engine")


def bucket_len(n: int, minimum: int = 32, maximum: int = 1 << 20) -> int:
    """Round up to a power of two within [minimum, maximum]."""
    return min(max(minimum, 1 << max(0, math.ceil(math.log2(max(n, 1))))), maximum)


def fit_request(
    prompt: list[int], max_new: int, limit: int
) -> tuple[list[int], int]:
    """Clamp (prompt, max_new) so prompt + generation + 1 fits in a
    `limit`-length KV cache: keeps the prompt tail, then caps max_new.
    Prevents silent out-of-bounds cache writes (dropped inside jit)."""
    if len(prompt) + max_new + 1 > limit:
        keep = max(1, limit - max_new - 1)
        prompt = prompt[-keep:]
        max_new = max(1, min(max_new, limit - len(prompt) - 1))
    return prompt, max_new


def _adapt_specs(specs, shapes, mesh: Mesh, observer=None):
    """Null out spec axes that don't divide the actual dims (vocab sizes
    and tiny test models aren't always multiples of the mesh).
    `observer(where, dim, entry, size, axis)` is called for every real
    downgrade (a named axis replaced by replication) with the leaf's
    tree path — the engine counts and logs these so a silently
    replicated weight can never masquerade as TP serving."""
    if observer is None:
        return jax.tree_util.tree_map(
            lambda s, x: mesh_mod.compatible_spec(s, x.shape, mesh),
            specs, shapes,
        )

    def adapt(path, s, x):
        where = jax.tree_util.keystr(path)
        return mesh_mod.compatible_spec(
            s, x.shape, mesh,
            on_downgrade=lambda dim, entry, size, axis: observer(
                where, dim, entry, size, axis
            ),
        )

    return jax.tree_util.tree_map_with_path(adapt, specs, shapes)


def _shard_params(params, specs, mesh: Mesh, observer=None):
    specs = _adapt_specs(specs, params, mesh, observer=observer)
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs
    )


def _sharded_init(init_fn, specs, mesh: Mesh, key, observer=None):
    """jit the initializer with mesh-adapted output shardings.

    CAVEAT (docs/tensor_parallel_serving.md): random bits generated
    inside a jit whose output shards its LEADING dim (e.g. the
    vocab-sharded embed) depend on the partitioning — random-INIT
    weights are therefore NOT reproducible across mesh shapes.
    Cross-mesh bit-identity claims must feed both engines the same
    weights (a checkpoint, or one host-side init tree); init here is
    for serving models whose values don't matter (warmup, synthetic
    perf staging, the random-llama3-8b fallback on ONE mesh)."""
    shapes = jax.eval_shape(init_fn, key)
    specs = _adapt_specs(specs, shapes, mesh, observer=observer)
    with mesh:
        params = jax.jit(
            init_fn,
            out_shardings=jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), specs
            ),
        )(key)
    jax.block_until_ready(params)
    return params


# What a family cannot be composed with yet, and why. The ONE place a
# composition is refused by family (GenerationEngine._check_family,
# run first in the constructor); the dense llama family has every
# feature but the float8 cache.
_MOE_ROUTING = (
    "its capacity dispatch is batch-global, so a row's output depends "
    "on the other rows"
)
_LATENT_CACHE = (
    "these paths move K/V pairs of [kv_heads, head_dim]; this family "
    "caches one latent plane"
)
_THREE_PLANES = (
    "the page payload and the tier snapshot hold a K and a V plane; "
    "this family's page has a third, the indexer's keys"
)
_FP8_CACHE = (
    "a float8 plane is read by the latent and keye families' block "
    "walks only; these families' attention reads int8 pages through "
    "their scales"
)
_MESH_WITH_INDEXER = (
    "a mesh of more than one device, for a model with a "
    "sparse-attention indexer"
)
_MESH_WITH_EXPERT_SHARE = (
    "a mesh of more than one device, for a model that holds a "
    "share of its experts"
)
_TWO_KINDS_OF_PAGE = (
    "these paths move pages of one arena; this family's window layers "
    "keep theirs in a second arena under a free rule by position"
)
_MESH_WITH_ROW_STATE = (
    "a mesh of more than one device, for a model with state-space layers"
)
_STATE_NOT_IN_PAGES = (
    "a row's recurrent state lives in the device's state pool beside "
    "the pages and only pages travel: a state through the host tier "
    "or over TransferKV is not built"
)
_UNSUPPORTED = {
    "llama": {"kv_cache_dtype fp8": _FP8_CACHE},
    "moe": {
        "kv_cache_dtype fp8": _FP8_CACHE,
        "lora": "the adapter delta sits on the dense family's fused qkv",
        "pipeline-parallel serving (mesh.stage > 1)": _MOE_ROUTING,
        "batching.paged_kv": (
            "its forward scans the cache in and out a layer and has no "
            "block-table path; the dropless mla_moe family has"
        ),
    },
    "mla_moe": {
        "lora": "the adapter delta sits on the dense family's fused qkv",
        "pipeline-parallel serving (mesh.stage > 1)": (
            "the staged forward runs one homogeneous layer stack"
        ),
        "kv_ring": "the model has no sliding window",
        "batching.kv_tiers": _LATENT_CACHE,
        "batching.paged_kv_host_bytes (the host tier)": _LATENT_CACHE,
        "a non-mixed serving.role (KV export/import)": _LATENT_CACHE,
        "quantize / synthetic_weights": (
            "the weights are served in bf16; int8 matmuls are wired "
            "into the dense family's projections only"
        ),
        # Asked of the family's members with a sparse-attention indexer
        # (`index_topk`) or a share of the experts (`experts_held`),
        # once the mesh is built (_MESH_REFUSALS).
        _MESH_WITH_INDEXER: (
            "a row's top-k runs over its whole indexer plane and the "
            "selected latents are gathered by token index; neither is "
            "built for a sharded cache"
        ),
        _MESH_WITH_EXPERT_SHARE: (
            "the chip computes its own experts' part of a layer and "
            "the exchange of tokens and partial sums over a mesh axis "
            "is not built; on one chip the layer runs without it"
        ),
    },
    "keye": {
        "lora": "the adapter delta sits on the dense family's fused qkv",
        "pipeline-parallel serving (mesh.stage > 1)": (
            "the staged forward threads two cache planes through the "
            "dense layer; this family's layer has three and experts"
        ),
        "kv_ring": "the model has no sliding window",
        "batching.kv_tiers": _THREE_PLANES,
        "batching.paged_kv_host_bytes (the host tier)": _THREE_PLANES,
        "a non-mixed serving.role (KV export/import)": _THREE_PLANES,
        "quantize / synthetic_weights": (
            "the weights are served in bf16; int8 matmuls are wired "
            "into the dense family's projections only"
        ),
        _MESH_WITH_INDEXER: (
            "a row's top-k runs over its whole indexer plane and the "
            "selected K and V are gathered by token index; neither is "
            "built for a sharded cache"
        ),
    },
    "jamba": {
        "lora": "the adapter delta sits on the dense family's fused qkv",
        "pipeline-parallel serving (mesh.stage > 1)": (
            "the staged forward runs one homogeneous layer stack; this "
            "family's is runs of state-space layers around attention "
            "layers"
        ),
        "kv_ring": "the model has no sliding window",
        "batching.kv_tiers": (
            "a tier's batcher holds a pool of row states of its own and "
            "a request does not move between tiers with its state"
        ),
        "batching.paged_kv_host_bytes (the host tier)": _STATE_NOT_IN_PAGES,
        "a non-mixed serving.role (KV export/import)": _STATE_NOT_IN_PAGES,
        "quantize / synthetic_weights": (
            "the weights are served in bf16; int8 matmuls are wired "
            "into the dense family's projections only"
        ),
        "kv_cache_dtype (other than the model's)": (
            "the two attention layers' pages are served in bf16 and the "
            "recurrence's state in float32; no lower precision of either "
            "has been held to the reference"
        ),
        "batching.prefill_interleave": (
            "a chunk that rides a tick would have to carry the "
            "admitting row's state between ticks; only the admission "
            "programs do"
        ),
        _MESH_WITH_ROW_STATE: (
            "the state pool and the selective scan are whole on one "
            "chip; a state-space layer on a mesh is not built"
        ),
    },
    "smallthinker": {
        "lora": "the adapter delta sits on the dense family's fused qkv",
        "pipeline-parallel serving (mesh.stage > 1)": (
            "the staged forward runs one homogeneous layer stack; this "
            "family's is periods of a full layer and window layers, "
            "each kind with an arena of its own"
        ),
        "kv_ring": (
            "the full layers attend every key; the window layers' pages "
            "are let go by position under batching.paged_kv"
        ),
        "batching.kv_tiers": _TWO_KINDS_OF_PAGE,
        "batching.paged_kv_host_bytes (the host tier)": _TWO_KINDS_OF_PAGE,
        "a non-mixed serving.role (KV export/import)": _TWO_KINDS_OF_PAGE,
        "quantize / synthetic_weights": (
            "the weights are served in bf16; int8 matmuls are wired "
            "into the dense family's projections only"
        ),
        "kv_cache_dtype int8": (
            "the layer stack scans plain planes a period; pages of int8 "
            "values and scales have not been held to the reference "
            "(float8 pages are the benchmark's control)"
        ),
        "batching.prefill_interleave": (
            "a chunk that rides a tick is merged a row at a time into "
            "one arena; the window layers' live tail goes through "
            "_paged_put alone"
        ),
    },
}

# (config key, `_UNSUPPORTED` feature): refused where the key is set
# and the mesh holds more than one device.
_MESH_REFUSALS = (
    ("index_topk", _MESH_WITH_INDEXER),
    ("experts_held", _MESH_WITH_EXPERT_SHARE),
    ("row_state", _MESH_WITH_ROW_STATE),
)


class GenerationEngine:
    """Decoder-family generation (any family of `models/__init__.py`
    but the embedding one): prefill + decode + fused generate. The
    family module supplies init_params / param_specs / forward /
    cache_specs with a shared contract."""

    def __init__(
        self,
        cfg: llama_mod.LlamaConfig,
        serving: Optional[ServingConfig] = None,
        mesh: Optional[Mesh] = None,
        params=None,
        seed: int = 0,
    ):
        from ggrmcp_tpu.models import family_module

        self.cfg = cfg
        self.fam = family_module(cfg)
        self.serving = serving or ServingConfig()
        self._check_family(mesh)
        if self.serving.failpoints:
            # Deterministic fault injection (utils/failpoints.py):
            # config-armed here, at the serving plane's root, so every
            # entry point — sidecar, bench, a test-built engine — gets
            # the same chaos schedule without extra wiring. (The
            # GGRMCP_FAILPOINTS env var arms the same registry at
            # import time.)
            from ggrmcp_tpu.utils import failpoints

            failpoints.registry.arm_spec(self.serving.failpoints)
        self.mesh = mesh if mesh is not None else mesh_mod.build_mesh(
            self.serving.mesh
        )
        if self.mesh.devices.size > 1:
            for key, feature in _MESH_REFUSALS:
                if getattr(cfg, key, None):
                    self._refuse(feature)
        # Sharding-downgrade accounting (tensor-parallel serving,
        # docs/tensor_parallel_serving.md): every spec axis
        # compatible_spec replaces with replication is counted and
        # logged — the `mesh_spec_downgrades` ServingStats gauge — so a
        # fallback to replicated weights is always observable, never a
        # masquerade of TP serving.
        self.spec_downgrades = 0
        self._downgrades_seen: set = set()
        # The Pallas flash kernel is a custom call GSPMD cannot
        # partition. Single-device: auto-select (None). Multi-device
        # TPU meshes whose sharding the kernel CAN take manually
        # (batch over data/fsdp, heads over tensor; no sequence/
        # expert/stage sharding) get flash via the shard_map wrapper
        # (flash_attention_sharded); anything else forces XLA.
        if self.mesh.devices.size == 1:
            self.use_flash, self.flash_mesh = None, None
        else:
            sizes = self.mesh.shape
            shardable = (
                self.mesh.devices.flat[0].platform == "tpu"
                and cfg.num_kv_heads % sizes.get("tensor", 1) == 0
                and sizes.get("sequence", 1) == 1
                and sizes.get("expert", 1) == 1
                and sizes.get("stage", 1) == 1
            )
            self.flash_mesh = self.mesh if shardable else None
            self.use_flash = None if shardable else False
        self.kv_dtype = self.serving.kv_cache_dtype
        if self.kv_dtype:
            # Materializing a bf16 cache for the Pallas kernel would
            # forfeit the int8 bandwidth win — the XLA path fuses the
            # cast+scale into the attention matmuls instead.
            self.use_flash, self.flash_mesh = False, None
        # Ring-buffer KV (sliding-window models, batcher path only):
        # the shared cache capacity is window + prefill_chunk - 1 (the
        # static clobber bound for chunked steps), and request length
        # is bounded by the RoPE range instead of the cache.
        self.ring_capacity = None
        if getattr(self.serving, "kv_ring", False):
            if not getattr(cfg, "sliding_window", None):
                raise ValueError(
                    f"kv_ring requires a sliding-window model; "
                    f"{cfg.name} has none"
                )
            cap = (
                cfg.sliding_window + self.serving.batching.prefill_chunk - 1
            )
            if cap > cfg.max_seq_len:
                # Clamping instead would violate the trace-time clobber
                # bound the model layer asserts (C >= W + chunk - 1).
                raise ValueError(
                    f"kv_ring: sliding_window ({cfg.sliding_window}) + "
                    f"prefill_chunk "
                    f"({self.serving.batching.prefill_chunk}) - 1 = {cap} "
                    f"exceeds max_seq_len ({cfg.max_seq_len}); lower "
                    f"batching.prefill_chunk"
                )
            self.ring_capacity = cap
        self._init_sp_prefill()
        self._init_pp_serving()
        # kv_ring composes with pp serving (round 3): the staged
        # forward threads `ring` into each stage's layer block, so
        # mod-C writes + absolute-position masking apply per stage
        # (parallel/pipeline.py::_run_block_cached).
        # int8 KV composes with PP serving: the staged forward's cache
        # bookkeeping goes through quant.kv_map, so QuantizedArray K/V
        # leaves thread the tick schedule like dense ones
        # (parallel/pipeline.py::_pipelined_cached).
        param_specs = (
            self._pp.param_specs_pp(cfg) if self.pp_serving
            else self.fam.param_specs(cfg)
        )
        self._param_specs = param_specs
        if params is None and self.serving.synthetic_weights:
            # Perf staging: the quantized structure is initialized
            # directly, so the quantize pass below must not run again.
            params = self._synthetic_int8_init(seed)
        else:
            if params is None:
                t0 = time.monotonic()
                params = _sharded_init(
                    partial(self.fam.init_params, cfg=cfg),
                    param_specs, self.mesh,
                    jax.random.PRNGKey(seed),
                    observer=self._note_downgrade,
                )
                logger.info(
                    "initialized %s: %.1fM params in %.1fs",
                    cfg.name, count_params(params) / 1e6, time.monotonic() - t0,
                )
            else:
                params = _shard_params(
                    params, param_specs, self.mesh,
                    observer=self._note_downgrade,
                )
            if self.serving.quantize:
                params = self._quantize_params(params)
        params = self._init_lora(params, seed)
        self.params = params
        if self.adapter_arena is not None:
            # Every successful arena load reinstalls the (new) factor
            # arrays into params — the next device call serves them;
            # shapes/shardings are load-invariant so no program ever
            # recompiles for a new adapter.
            self.adapter_arena.attach_commit(self._install_lora_rows)
        # Weights ride as explicit jit ARGUMENTS, never closure
        # captures: a closed-over param tree is embedded into the
        # lowered module as constants (jax warns past 2 GB — llama3-8b
        # int8 is 8 GB of HLO), which bloats compile time/memory and
        # keys the persistent compile cache on weight VALUES, so no
        # cache hit ever lands across processes. As arguments the
        # executable is weight-independent and the cache key is shapes
        # + shardings only.
        self._prefill_fn = jax.jit(
            self._prefill_impl, donate_argnums=(3,), static_argnums=()
        )
        self._decode_fn = jax.jit(
            self._decode_impl, donate_argnums=(2,), static_argnums=(5,)
        )
        # bound method: args are (params, tokens, true_len, max_new,
        # sampling, rng, eos_id) — max_new and sampling are static.
        self._generate_fn = jax.jit(
            self._generate_impl, static_argnums=(3, 4)
        )
        self._init_ledger()

    def _init_ledger(self) -> None:
        """Device-memory ledger + compile watcher (obs plane,
        docs/observability.md). The engine owns the ledger — batchers
        built over it register their components into the same instance
        (per-tier scopes) so one reconcile() closes over the whole
        serving stack. Suppliers read live attributes, so quantize/
        LoRA rebuilds are accounted automatically. Obs-off:
        the ledger registers nothing and the watcher never installs —
        zero work, like the flight recorder's disabled hooks."""
        from ggrmcp_tpu.serving import compile_watcher
        from ggrmcp_tpu.serving.memory_ledger import MemoryLedger

        obs = getattr(self.serving, "observability", None)
        enabled = bool(obs.enabled) if obs is not None else True
        self.ledger = MemoryLedger(enabled=enabled)
        self.ledger.register("weights", self._ledger_weights)
        if self.adapter_arena is not None:
            # Dynamic arena: the `lora` supplier reads the ARENA's
            # arrays, not a params scan — the arena owns the rows and
            # params holds the same objects (reconcile attributes by
            # identity; _ledger_weights excludes the lora_ keys).
            self.adapter_arena.register_ledger(self.ledger)
        elif self.lora_enabled:
            self.ledger.register("lora", self._ledger_lora)
        if enabled:
            compile_watcher.watcher.install()
            # A fresh engine opens a new warmup era: its cold compiles
            # are expected, not steady-state recompiles (the sidecar
            # re-marks warm when ITS warmup finishes).
            compile_watcher.watcher.mark_cold()

    def _ledger_weights(self):
        """The model's parameters (LoRA factors excluded — they are
        their own component)."""
        params = self.params
        if self.lora_enabled and isinstance(params, dict):
            params = {
                **params,
                "layers": {
                    k: v for k, v in params["layers"].items()
                    if not k.startswith("lora_")
                },
            }
        return params

    def _ledger_lora(self):
        """The stacked per-adapter factor arrays inside params (the
        boot-time static mode; the dynamic arena registers its own
        supplier — AdapterArena.register_ledger)."""
        if not self.lora_enabled or not isinstance(self.params, dict):
            return None
        return {
            k: v for k, v in self.params["layers"].items()
            if k.startswith("lora_")
        }

    def _note_downgrade(
        self, where: str, dim: int, entry, size: int, axis: int
    ) -> None:
        """compatible_spec dropped a real sharding axis for `where` —
        count it (the mesh_spec_downgrades gauge) and log it. The count
        is per distinct (leaf, dim) SITE — cache builders re-run per
        batcher/stream, and a per-call count would inflate an
        unchanging condition into an ever-growing gauge."""
        key = (where, dim)
        if key not in self._downgrades_seen:
            self._downgrades_seen.add(key)
            self.spec_downgrades += 1
            logger.warning(
                "mesh spec downgrade: %s dim %d (size %d) not divisible "
                "by mesh axis %r (size %d) — replicated instead of "
                "sharded (watch gauge mesh_spec_downgrades)",
                where or "<leaf>", dim, size, entry, axis,
            )

    def _observe_cache_spec(self, where, dim, entry, size, axis) -> None:
        """compatible_spec observer for KV-cache layouts (batch-dim
        drops on tiny test batches are expected; a KV-HEAD drop — GQA
        heads not divisible by the tensor axis — is the one that turns
        sharded attention into replicated attention)."""
        self._note_downgrade(where, dim, entry, size, axis)

    def lora_stats(self) -> dict:
        """ServingStats lora_* scalars. Arena mode: the live registry/
        residency/load counters; static boot-time mode: the configured
        set is both registered and resident (loads/evictions are
        structurally zero — that is what "frozen at boot" means); LoRA
        off: all zeros (the proto-drift contract wants every key)."""
        if self.adapter_arena is not None:
            return self.adapter_arena.stats()
        n = len(self.lora_names)
        return {
            "lora_adapters_registered": n,
            "lora_adapters_resident": n,
            "lora_rows_total": n,
            "lora_loads": 0,
            "lora_evictions": 0,
            "lora_hits": 0,
            "lora_load_ms": 0.0,
            "lora_shed": 0,
        }

    def mesh_stats(self) -> dict:
        """Mesh identity for ServingStats / the bench artifact: tensor
        chips, total devices, the human-readable shape, and how many
        sharding specs were downgraded to replication (0 = every spec
        landed as written — real TP serving)."""
        return {
            "tp_chips": mesh_mod.axis_size(self.mesh, "tensor"),
            "mesh_devices": int(self.mesh.devices.size),
            "mesh_shape": mesh_mod.mesh_shape_str(self.mesh),
            "mesh_spec_downgrades": self.spec_downgrades,
        }

    def _init_lora(self, params, seed: int):
        """Multi-LoRA serving (ops/lora.py): stack per-adapter factors
        into params["layers"] so the layer scan slices them with every
        other stacked weight. Runs AFTER quantization — adapter factors
        stay in the model dtype (they are tiny; int8 would buy nothing
        and cost accuracy). Row 0 is the base no-op adapter.

        Two modes (config.LoraConfig):
        - boot-time `adapters`: the historical static list — rows fixed
          at init, names resolved via `resolve_adapter`.
        - dynamic `registry` (serving/adapter_arena.py): a disk
          registry of `.npz` factor pairs discoverable at RUNTIME, a
          fixed-shape device arena of `arena_rows` resident rows, and
          refcount/LRU residency managed per request — resolution goes
          through the batcher's serialized `acquire_adapter` stream,
          never this method."""
        self.lora_names: dict[str, int] = {}
        self.adapter_arena = None
        adapters = list(self.serving.lora.adapters)
        registry = getattr(self.serving.lora, "registry", "")
        self.lora_enabled = bool(adapters) or bool(registry)
        if not self.lora_enabled:
            return params
        if adapters and registry:
            raise ValueError(
                "lora.registry and lora.adapters are mutually exclusive "
                "(config.validate mirrors this)"
            )
        if self.pp_serving:
            raise ValueError(
                "lora does not compose with pipeline-parallel serving "
                "yet (the staged layer loop would need per-stage idx "
                "threading)"
            )
        if self.serving.lora.rank < 1:
            raise ValueError("lora.rank must be >= 1")
        if registry:
            from ggrmcp_tpu.serving.adapter_arena import AdapterArena

            self.adapter_arena = AdapterArena(
                registry,
                int(getattr(self.serving.lora, "arena_rows", 8)),
                self.serving.lora.rank,
                self.cfg,
                mesh=self.mesh,
            )
            params["layers"] = {
                **params["layers"],
                "lora_qkv_a": self.adapter_arena.a_dev,
                "lora_qkv_b": self.adapter_arena.b_dev,
            }
            logger.info(
                "lora arena: %d device rows over registry %s (rank %d, "
                "%d adapter(s) registered, %.1f MB resident)",
                self.adapter_arena.rows, registry, self.serving.lora.rank,
                len(self.adapter_arena.registered()),
                (self.adapter_arena.a_dev.nbytes
                 + self.adapter_arena.b_dev.nbytes) / 1e6,
            )
            return params
        if len(set(adapters)) != len(adapters) or "" in adapters:
            raise ValueError("lora.adapters must be unique, non-empty names")
        for name in adapters:
            # Names become `{lora.path}/{name}.npz` — separators would
            # let a config read factors from outside the directory.
            if "/" in name or "\\" in name or name.startswith("."):
                raise ValueError(
                    f"lora adapter name {name!r} must be a plain name "
                    f"(no path separators or leading dots)"
                )
        from ggrmcp_tpu.ops import lora as lora_mod

        factors = lora_mod.init_lora_layers(
            jax.random.PRNGKey(seed + 7), self.cfg, len(adapters),
            self.serving.lora.rank,
        )
        with self.mesh:
            factors = {
                k: jax.device_put(
                    v, NamedSharding(self.mesh, P())
                ) for k, v in factors.items()
            }
        params["layers"] = {**params["layers"], **factors}
        self.lora_names = {name: i + 1 for i, name in enumerate(adapters)}
        logger.info(
            "lora serving: %d adapter(s) %s, rank %d (%.1f MB of factors)",
            len(adapters), adapters, self.serving.lora.rank,
            sum(v.nbytes for v in factors.values()) / 1e6,
        )
        if self.serving.lora.path:
            self.params = params  # set_lora_weights reads/writes it
            self._load_lora_dir(self.serving.lora.path)
            params = self.params
        return params

    def _load_lora_dir(self, path: str) -> None:
        """Load trained factors from `{path}/{name}.npz` (arrays `a`,
        `b`; LoraConfig.path contract). A missing file leaves that
        adapter a zero-init no-op; a present-but-wrong file is a
        configuration error and fails loudly."""
        import os

        for name in self.lora_names:
            f = os.path.join(path, f"{name}.npz")
            if not os.path.exists(f):
                logger.info("lora: no factors at %s (adapter stays no-op)", f)
                continue
            with np.load(f) as data:
                try:
                    self.set_lora_weights(name, data["a"], data["b"])
                except (KeyError, ValueError) as exc:
                    raise ValueError(f"lora factors {f}: {exc}") from exc
            logger.info("lora: loaded %s", f)

    def _install_lora_rows(self) -> None:
        """AdapterArena commit hook: point params["layers"] at the
        arena's current factor arrays. Callers pass params as a jit
        ARGUMENT, so in-flight device calls keep their old (immutable)
        arrays and the next dispatch serves the loaded rows."""
        arena = self.adapter_arena
        self.params = {
            **self.params,
            "layers": {
                **self.params["layers"],
                "lora_qkv_a": arena.a_dev,
                "lora_qkv_b": arena.b_dev,
            },
        }

    def n_adapter_rows(self) -> int:
        """Highest valid per-request adapter row id (0 = base). Static
        mode: the configured adapter count; arena mode: the arena's
        device rows (row validity, not residency — residency is the
        arena's job)."""
        if self.adapter_arena is not None:
            return self.adapter_arena.rows
        return len(self.lora_names)

    def resolve_adapter(self, name: str) -> int:
        """Adapter name → served row id (0 = base; raises on unknown).
        STATIC mode only: the dynamic arena resolves names through the
        batcher's serialized acquire stream (a resolution there may
        load factors H2D, which must land between ticks — use
        ContinuousBatcher.acquire_adapter / AdapterArena.acquire)."""
        if not name:
            return 0
        if self.adapter_arena is not None:
            raise ValueError(
                "dynamic adapter arena: resolve adapter names via "
                "AdapterArena.acquire (batcher.acquire_adapter on "
                "serving paths), not resolve_adapter"
            )
        try:
            return self.lora_names[name]
        except KeyError:
            raise ValueError(
                f"unknown adapter {name!r}; configured: "
                f"{sorted(self.lora_names)}"
            ) from None

    def set_lora_weights(self, name: str, a, b) -> None:
        """Install trained factors for a configured adapter: a
        [L, D, r], b [L, r, (H+2KVH)*Dh] (pre-scaled by alpha/r).
        Row 0 (base) cannot be written."""
        idx = self.resolve_adapter(name)
        if idx == 0:
            raise ValueError("cannot overwrite the base adapter row")
        layers = dict(self.params["layers"])
        dtype = self.cfg.jnp_dtype
        a = jnp.asarray(a, dtype)
        b = jnp.asarray(b, dtype)
        # Explicit shape checks: .at[].set broadcasts, so e.g. a single
        # layer's [D, r] would silently install identical factors in
        # every layer instead of erroring.
        want_a = layers["lora_qkv_a"].shape[0:1] + layers[
            "lora_qkv_a"
        ].shape[2:]
        want_b = layers["lora_qkv_b"].shape[0:1] + layers[
            "lora_qkv_b"
        ].shape[2:]
        if a.shape != want_a or b.shape != want_b:
            raise ValueError(
                f"factor shapes {a.shape}/{b.shape} != expected "
                f"{want_a}/{want_b}"
            )
        layers["lora_qkv_a"] = layers["lora_qkv_a"].at[:, idx].set(a)
        layers["lora_qkv_b"] = layers["lora_qkv_b"].at[:, idx].set(b)
        self.params = {**self.params, "layers": layers}

    def _init_sp_prefill(self) -> None:
        """Sequence-parallel prefill (SURVEY §5.7): when the mesh has a
        `sequence` axis > 1, fresh prefills of >= sp_prefill_min_seq
        tokens run attention via ring (ppermute K/V rotation) or
        Ulysses (all_to_all head re-shard) instead of the local XLA
        path — the long-prompt serving integration the round-1 verdict
        flagged (ops/ring_attention.py had no serving caller)."""
        from ggrmcp_tpu.ops import ring_attention as ring_mod

        self._sp_n = mesh_mod.axis_size(self.mesh, "sequence")
        mode = self.serving.sp_prefill
        # int8 KV composes: the sp path attends the int8 round-tripped
        # step K/V (models/llama.py::attention_block k_step), so sp and
        # XLA prefill of one prompt carry identical quantization error.
        # Sliding window composes too (round 3): ring masks by global
        # position, Ulysses gathers full sequences — the model layer
        # passes cfg.sliding_window through the attn_impl contract.
        if mode and self.serving.kv_ring and self._sp_n > 1:
            # Ring-capacity caches violate the sp fresh-prefill
            # contract (cache sized exactly to the chunk) — a prompt
            # longer than the ring would wrap mid-prefill.
            raise ValueError(
                "sp_prefill does not compose with kv_ring: ring-capacity "
                "caches break the fresh-prefill cache-sized-to-chunk "
                "contract (chunked admission serves long prompts instead)"
            )
        self.sp_prefill = mode if (self._sp_n > 1 and mode) else ""
        self.sp_min_seq = self.serving.sp_prefill_min_seq
        if not self.sp_prefill:
            self._sp_attn = None
            return
        if mode == "ulysses" and self.cfg.num_heads % self._sp_n != 0:
            raise ValueError(
                f"ulysses sp_prefill needs heads ({self.cfg.num_heads}) "
                f"divisible by the sequence axis ({self._sp_n})"
            )
        impl = (
            ring_mod.ring_attention if mode == "ring"
            else ring_mod.ulysses_attention
        )
        mesh = self.mesh

        def sp_attn(q, k, v, causal=True, window=None):
            return impl(q, k, v, mesh, causal=causal, window=window)

        self._sp_attn = sp_attn

    def _check_family(self, mesh) -> None:
        """Refuse, by name, every composition this model's family does
        not have (`_UNSUPPORTED`)."""
        from ggrmcp_tpu.models import family_name

        family = family_name(self.cfg)
        refused = _UNSUPPORTED.get(family)
        if not refused:
            return
        sv, bt = self.serving, self.serving.batching
        stages = (
            mesh.shape.get("stage", 1) if mesh is not None
            else max(1, int(getattr(sv.mesh, "stage", 1) or 1))
        )
        asked = {
            "lora": bool(sv.lora.adapters)
            or bool(getattr(sv.lora, "registry", "")),
            "pipeline-parallel serving (mesh.stage > 1)": stages > 1,
            "kv_ring": bool(getattr(sv, "kv_ring", False)),
            "batching.kv_tiers": bool(bt.kv_tiers),
            "batching.paged_kv_host_bytes (the host tier)": bool(
                bt.paged_kv_host_bytes),
            "a non-mixed serving.role (KV export/import)": getattr(
                sv, "role", "mixed") != "mixed",
            "quantize / synthetic_weights": bool(sv.quantize)
            or bool(sv.synthetic_weights),
            "kv_cache_dtype fp8": sv.kv_cache_dtype == "fp8",
            "kv_cache_dtype int8": sv.kv_cache_dtype == "int8",
            "kv_cache_dtype (other than the model's)": bool(
                sv.kv_cache_dtype),
            "batching.prefill_interleave": getattr(
                bt, "prefill_interleave", "off") == "on",
        }
        for feature in refused:
            if asked.get(feature):
                self._refuse(feature)

    def _refuse(self, feature: str) -> None:
        """Raise if `_UNSUPPORTED` lists `feature` for this family."""
        from ggrmcp_tpu.models import family_name

        family = family_name(self.cfg)
        why = _UNSUPPORTED.get(family, {}).get(feature)
        if why:
            raise ValueError(
                f"{feature} is not supported for the {family} family "
                f"(model {self.cfg.name}): {why}"
            )

    def prefill_forward(self, params, tokens, cache, valid=None,
                        lora_idx=None, logit_idx=None, capture=None):
        """fam.forward for FRESH prefill (cache written from offset 0 —
        the attn_impl contract, models/llama.py::attention_block).
        Dispatches to the sequence-parallel path when configured and
        the chunk is long enough; callers (engine + batcher admission)
        use this instead of fam.forward for first-prefill."""
        if self.pp_serving:
            return self._pp.pipeline_forward_cached(
                params, self.cfg, tokens, cache, self.mesh
            )
        s = tokens.shape[1]
        sp = (
            self._sp_attn is not None
            and self.fam is llama_mod
            and s >= self.sp_min_seq
            and s % self._sp_n == 0
        )
        if sp:
            return llama_mod.forward(
                params, self.cfg, tokens, cache, attn_impl=self._sp_attn,
                lora_idx=lora_idx,
            )
        return self.decode_forward(
            params, tokens, cache, valid=valid, lora_idx=lora_idx,
            logit_idx=logit_idx, capture=capture,
        )

    def _init_pp_serving(self) -> None:
        """Serving under pipeline parallelism: when the mesh has a
        `stage` axis > 1, prefill AND decode run the staged cached
        forward (parallel/pipeline.py::pipeline_forward_cached) with
        the layer stack and KV cache sharded over `stage` — the
        serve-a-model-bigger-than-a-slice path. Dense Llama only."""
        from ggrmcp_tpu.parallel import pipeline as pp_mod

        self._pp = pp_mod
        self._pp_n = mesh_mod.axis_size(self.mesh, "stage")
        self.pp_serving = self._pp_n > 1  # llama only: _check_family
        if self.pp_serving and self.cfg.num_layers % self._pp_n != 0:
            raise ValueError(
                f"{self.cfg.num_layers} layers not divisible by "
                f"stage={self._pp_n}"
            )
        if self.pp_serving and self.sp_prefill:
            # One manual-collective scheme at a time: the staged
            # forward owns the layer loop.
            logger.warning("sp_prefill disabled under pipeline serving")
            self.sp_prefill = ""
            self._sp_attn = None

    def decode_forward(
        self, params, tokens, cache, valid=None, ring=False, lora_idx=None,
        logit_idx=None, with_stats=False, capture=None,
    ):
        """fam.forward for decode/extension steps (cache already has
        history). Dispatches to the staged path under PP. `ring` is
        per-call because it describes the CACHE's layout (the batcher's
        ring-capacity caches), not the engine: the engine's own
        contiguous request-sized caches keep ring=False. `lora_idx`:
        [B] per-row adapter ids (dense Llama, non-PP — the engine
        rejects LoRA configs elsewhere). `logit_idx` / `with_stats`:
        the mla_moe family's one-position head and routing counts
        (models/mla_moe.py::forward); callers pass them to that family
        only, which takes no `ring` and no `lora_idx` (_check_family
        refuses both). Every family hears the engine's word on
        attention kernels for its mesh (`use_flash`, `flash_mesh`).
        `capture`: which row states a ROW_STATE family's step copies
        into the pool as it passes them (models/jamba.py::forward)."""
        if self.pp_serving:
            return self._pp.pipeline_forward_cached(
                params, self.cfg, tokens, cache, self.mesh, ring=ring
            )
        if getattr(self.fam, "HEAD_AT_INDEX", False):  # mla_moe, keye, jamba
            more = {} if capture is None else {"capture": capture}
            return self.fam.forward(
                params, self.cfg, tokens, cache, valid=valid,
                logit_idx=logit_idx, with_stats=with_stats,
                use_flash=self.use_flash, flash_mesh=self.flash_mesh,
                **more,
            )
        if self.fam is moe_mod:
            return self.fam.forward(
                params, self.cfg, tokens, cache, valid=valid,
                use_flash=self.use_flash, flash_mesh=self.flash_mesh,
                ring=ring,
            )
        return self.fam.forward(
            params, self.cfg, tokens, cache, use_flash=self.use_flash,
            flash_mesh=self.flash_mesh, ring=ring, lora_idx=lora_idx,
        )

    def _synthetic_int8_init(self, seed: int):
        """Initialize the int8-quantized weight STRUCTURE directly with
        synthetic values (random int8 + small positive scales), never
        materializing dense weights (serving.synthetic_weights).

        Perf staging for models whose dense init exceeds the chip:
        llama3-8b bf16 is ~16 GB — all of a v5e-1's HBM — while its
        int8 form is ~8 GB. Throughput/MFU are weight-value independent
        (identical op graph, shapes, and HBM traffic), so the bench
        numbers are honest; the generated TEXT is meaningless, and the
        bench labels such runs `synthetic_weights: true`."""
        from ggrmcp_tpu.ops import quant

        if self.serving.quantize != "int8":  # config.validate mirrors this
            raise ValueError("synthetic_weights requires quantize='int8'")
        t0 = time.monotonic()
        qspecs = quant.quantize_specs(self._param_specs)
        shapes = jax.eval_shape(
            lambda k: quant.quantize_model(
                self.fam.init_params(k, self.cfg)
            ),
            jax.random.PRNGKey(seed),
        )
        qspecs = _adapt_specs(
            qspecs, shapes, self.mesh, observer=self._note_downgrade
        )
        leaves, treedef = jax.tree_util.tree_flatten(shapes)

        def gen(key):
            keys = jax.random.split(key, len(leaves))
            out = []
            for k, leaf in zip(keys, leaves):
                if leaf.dtype == jnp.int8:
                    out.append(
                        jax.random.randint(
                            k, leaf.shape, -127, 128, jnp.int32
                        ).astype(jnp.int8)
                    )
                else:
                    # scales and unquantized leaves (norms, embeddings):
                    # small positive magnitudes keep activations finite
                    out.append(
                        0.02 * jnp.abs(jax.random.normal(k, leaf.shape))
                        .astype(leaf.dtype) + jnp.asarray(1e-3, leaf.dtype)
                    )
            return jax.tree_util.tree_unflatten(treedef, out)

        with self.mesh:
            params = jax.jit(
                gen,
                out_shardings=jax.tree_util.tree_map(
                    lambda s: NamedSharding(self.mesh, s), qspecs
                ),
            )(jax.random.PRNGKey(seed))
        jax.block_until_ready(jax.tree_util.tree_leaves(params)[0])
        logger.info(
            "synthetic int8 init %s: %.1f MB of weights in %.1fs",
            self.cfg.name,
            quant.quantized_nbytes(params) / 1e6,
            time.monotonic() - t0,
        )
        return params

    def _quantize_params(self, params):
        """Int8 weight-only quantization, applied on-mesh (the transform
        runs under jit with quantized out-shardings, so full-precision
        weights never round-trip through the host)."""
        from ggrmcp_tpu.ops import quant

        if self.serving.quantize != "int8":
            raise ValueError(
                f"unknown quantize mode {self.serving.quantize!r}"
            )
        # The engine's ACTUAL placement specs (stage-sharded under PP):
        # quantizing with the non-staged specs would reshard every
        # layer off the stage axis — per-slice HBM ≈ full model, on
        # exactly the bigger-than-slice targets PP serves.
        qspecs = quant.quantize_specs(self._param_specs)
        shapes = jax.eval_shape(quant.quantize_model, params)
        qspecs = _adapt_specs(
            qspecs, shapes, self.mesh, observer=self._note_downgrade
        )
        before = quant.quantized_nbytes(params)
        with self.mesh:
            # Donate the dense params: XLA frees each full-precision
            # buffer as its int8 counterpart materializes, keeping peak
            # HBM ~1× the dense size instead of dense + quantized.
            params = jax.jit(
                quant.quantize_model,
                donate_argnums=(0,),
                out_shardings=jax.tree_util.tree_map(
                    lambda s: NamedSharding(self.mesh, s), qspecs
                ),
            )(params)
        logger.info(
            "quantized %s to int8: %.1f → %.1f MB of weights",
            self.cfg.name, before / 1e6, quant.quantized_nbytes(params) / 1e6,
        )
        return params

    # -- jitted bodies ------------------------------------------------------

    def _prefill_impl(self, params, tokens, true_len, cache, lora_idx):
        """tokens [B,S] right-padded; true_len [B]. Returns
        (last_logits [B,V], cache with length=true_len). Fresh-prefill
        only (cache length 0) — dispatches through prefill_forward so
        long chunks can run sequence-parallel. lora_idx [B]: per-row
        adapter ids (all-zeros = base model; pruned by XLA when the
        param tree carries no adapter factors)."""
        # Padding must not compete for expert capacity on MoE (routing
        # is batch-global); dense forwards are pad-invariant already.
        valid = jnp.arange(tokens.shape[1])[None, :] < true_len[:, None]
        logits, cache = self.prefill_forward(
            params, tokens, cache, valid=valid, lora_idx=lora_idx
        )
        idx = jnp.maximum(true_len - 1, 0)
        last = jnp.take_along_axis(
            logits, idx[:, None, None], axis=1
        )[:, 0]  # [B, V]
        cache = cache._replace(length=true_len)
        return last, cache

    def _decode_impl(
        self, params, tokens, cache, rng, step, sampling: SamplingConfig,
        lora_idx,
    ):
        """tokens [B,1] → (next [B], cache)."""
        logits, cache = self.decode_forward(
            params, tokens, cache, lora_idx=lora_idx
        )
        key = jax.random.fold_in(rng, step)
        next_tok = sample(logits[:, -1], key, sampling)
        return next_tok, cache

    def _generate_impl(
        self, params, tokens, true_len, max_new: int,
        sampling: SamplingConfig, rng, eos_id, lora_idx,
    ):
        """Fused prefill + scan-decode. Returns (out_tokens [B, max_new],
        out_len [B])."""
        b = tokens.shape[0]
        max_cache = tokens.shape[1] + max_new
        cache = llama_mod.KVCache.create(self.cfg, b, max_cache, self.kv_dtype)
        last_logits, cache = self._prefill_impl(
            params, tokens, true_len, cache, lora_idx
        )
        key0 = jax.random.fold_in(rng, 0)
        first = sample(last_logits, key0, sampling)  # [B]
        done0 = first == eos_id

        def step(carry, i):
            cur, cache, done = carry
            logits, cache = self.decode_forward(
                params, cur[:, None], cache, lora_idx=lora_idx
            )
            key = jax.random.fold_in(rng, i + 1)
            nxt = sample(logits[:, -1], key, sampling)
            nxt = jnp.where(done, eos_id, nxt)
            new_done = done | (nxt == eos_id)
            return (nxt, cache, new_done), nxt

        (_, _, done), rest = jax.lax.scan(
            step, (first, cache, done0), jnp.arange(max_new - 1)
        )
        out = jnp.concatenate([first[:, None], rest.T], axis=1)  # [B, max_new]
        # out_len = tokens up to and including first eos (or max_new)
        is_eos = out == eos_id
        any_eos = is_eos.any(axis=1)
        first_eos = jnp.argmax(is_eos, axis=1)
        out_len = jnp.where(any_eos, first_eos + 1, max_new)
        return out, out_len

    # -- public API ---------------------------------------------------------

    def make_cache(self, batch: int, max_len: int) -> llama_mod.KVCache:
        """Mesh-sharded KV cache in the model's geometry (PP-aware)."""
        cfg = self.cfg
        lead = (cfg.cache_layers, batch, max_len)
        specs = (
            self._pp.cache_specs_pp() if self.pp_serving
            else self.fam.cache_specs()
        )
        observe = partial(self._observe_cache_spec, "kv_cache")

        def kv_spec(spec, plane):
            kv_shape = lead + tuple(plane)
            scale_shape = kv_shape[:-1] + (1,)
            adapted = mesh_mod.compatible_spec(
                spec, kv_shape, self.mesh, on_downgrade=observe
            )
            if self.kv_dtype != "int8":
                return adapted
            # Quantized leaf: the scale tree mirrors the values
            # (quantize_specs pattern); its size-1 last axis drops any
            # non-dividing spec entry via compatible_spec.
            return quant.QuantizedArray(
                q=adapted,
                scale=mesh_mod.compatible_spec(spec, scale_shape, self.mesh),
            )

        specs = llama_mod.with_planes(
            specs,
            [kv_spec(spec, plane) for spec, plane in zip(
                llama_mod.cache_planes(specs), cfg.kv_planes)],
            length=mesh_mod.compatible_spec(specs.length, (batch,), self.mesh),
        )
        with self.mesh:
            return jax.jit(
                partial(
                    llama_mod.KVCache.create, cfg, batch, max_len,
                    self.kv_dtype,
                ),
                out_shardings=jax.tree_util.tree_map(
                    lambda s: NamedSharding(self.mesh, s), specs,
                ),
            )()

    def make_paged_cache(
        self, batch: int, max_len: int, n_pages: int, page_size: int,
        window_pages: int = 0,
    ) -> llama_mod.PagedKVCache:
        """Mesh-sharded paged KV arena + block tables (batching.paged_kv,
        docs/paged_kv.md). Pages shard heads over `tensor` only — a page
        is shared across slots, so the page axis cannot ride a batch
        axis. What a page holds is the family's (`cfg.kv_planes`): K/V
        of [kv_heads, head_dim], or one latent plane. Non-PP serving
        only (the staged forward doesn't thread block tables)."""
        if self.pp_serving:
            raise ValueError(
                "paged_kv does not compose with pipeline-parallel "
                "serving (the staged forward has no block-table path)"
            )
        self._refuse("batching.paged_kv")  # asked for by the batcher
        lead = (self.cfg.cache_kinds[0][0], n_pages, page_size)
        raw = self.fam.paged_cache_specs()
        observe = partial(self._observe_cache_spec, "paged_kv_arena")

        def kv_spec(spec, plane):
            kv_shape = lead + tuple(plane)
            scale_shape = kv_shape[:-1] + (1,)
            adapted = mesh_mod.compatible_spec(
                spec, kv_shape, self.mesh, on_downgrade=observe
            )
            if self.kv_dtype != "int8":
                return adapted
            return quant.QuantizedArray(
                q=adapted,
                scale=mesh_mod.compatible_spec(
                    spec, scale_shape, self.mesh
                ),
            )

        specs = llama_mod.with_planes(raw, [
            kv_spec(spec, plane) for spec, plane in zip(
                llama_mod.cache_planes(raw), self.cfg.kv_planes)])
        if raw.window is not None:
            # The second kind's arena (`window_pages` of them): the
            # same planes under the same specs, a table of its own.
            specs = specs._replace(window=llama_mod.WindowArena(
                specs.k, specs.v, raw.window.table))
        with self.mesh:
            return jax.jit(
                partial(
                    llama_mod.PagedKVCache.create, self.cfg, batch,
                    max_len, n_pages, page_size, self.kv_dtype,
                    window_pages,
                ),
                out_shardings=jax.tree_util.tree_map(
                    lambda s: NamedSharding(self.mesh, s), specs,
                ),
            )()

    def _pack_prompts(
        self, prompts: list[list[int]], max_new: int, limit: int
    ) -> tuple[np.ndarray, np.ndarray, int]:
        """Fit and right-pad prompts to a shape bucket. Returns
        (tokens [B, S], true_len [B], fitted max_new)."""
        fitted = [fit_request(p, max_new, limit) for p in prompts]
        prompts = [p for p, _ in fitted]
        max_new = min(m for _, m in fitted)
        b = len(prompts)
        s = bucket_len(max(len(p) for p in prompts), maximum=limit)
        tokens = np.zeros((b, s), dtype=np.int32)
        true_len = np.zeros((b,), dtype=np.int32)
        for i, p in enumerate(prompts):
            tokens[i, : len(p)] = p
            true_len[i] = len(p)
        return tokens, true_len, max_new

    @staticmethod
    def _decode_outputs(
        out: np.ndarray, out_len: np.ndarray, eos_id: int
    ) -> tuple[list[list[int]], list[str]]:
        """[B, N] buffer + per-row lengths → (token lists with trailing
        eos stripped, finish reasons)."""
        results, reasons = [], []
        for i in range(out.shape[0]):
            ids = out[i, : out_len[i]].tolist()
            if ids and ids[-1] == eos_id:
                ids = ids[:-1]
                reasons.append("stop")
            else:
                reasons.append("length")
            results.append(ids)
        return results, reasons

    def generate(
        self,
        prompts: list[list[int]],
        max_new_tokens: int = 128,
        sampling: SamplingConfig = SamplingConfig(),
        eos_id: int = 2,
        seed: int = 0,
        adapters: Optional[list] = None,
    ) -> tuple[list[list[int]], list[str]]:
        """Batch generation via the fused path. Returns (token lists,
        finish reasons). `adapters`: per-prompt LoRA adapter names (or
        served ids); None/"" rows ride the base model."""
        tokens, true_len, max_new_tokens = self._pack_prompts(
            prompts, max_new_tokens, self.cfg.max_seq_len
        )
        if adapters and len(adapters) > len(prompts):
            raise ValueError(
                f"{len(adapters)} adapters for {len(prompts)} prompts"
            )
        idx = np.zeros((tokens.shape[0],), np.int32)
        leases: list = []
        try:
            for i, name in enumerate(adapters or []):
                if isinstance(name, int):
                    # Range-check explicitly: jnp.take clips
                    # out-of-range gathers, which would silently serve
                    # the WRONG adapter.
                    if not 0 <= name <= self.n_adapter_rows():
                        raise ValueError(
                            f"adapter id {name} out of range "
                            f"(0..{self.n_adapter_rows()})"
                        )
                    idx[i] = name
                elif self.adapter_arena is not None:
                    # Pin through the call: a concurrent churn eviction
                    # must never rewrite a row this batch is using.
                    lease = self.adapter_arena.acquire(name or "")
                    leases.append(lease)
                    idx[i] = lease.row
                else:
                    idx[i] = self.resolve_adapter(name or "")
            with self.mesh:
                out, out_len = self._generate_fn(
                    self.params, jnp.asarray(tokens),
                    jnp.asarray(true_len), max_new_tokens, sampling,
                    jax.random.PRNGKey(seed), jnp.int32(eos_id),
                    jnp.asarray(idx),
                )
            out, out_len = np.asarray(out), np.asarray(out_len)
        finally:
            for lease in leases:
                self.adapter_arena.release(lease)
        return self._decode_outputs(out, out_len, eos_id)

    def generate_stream(
        self,
        prompt: list[int],
        max_new_tokens: int = 128,
        sampling: SamplingConfig = SamplingConfig(),
        eos_id: int = 2,
        seed: int = 0,
        adapter: str = "",
    ) -> Iterator[int]:
        """Single-sequence streaming: per-step jitted decode, yields
        token ids as they are sampled. `adapter`: LoRA adapter name
        ("" = base; arena mode pins the row for the stream's life)."""
        lease = None
        if self.adapter_arena is not None and adapter:
            lease = self.adapter_arena.acquire(adapter)
            row = lease.row
        else:
            row = self.resolve_adapter(adapter)
        lora_idx = jnp.asarray([row], jnp.int32)
        prompt, max_new_tokens = fit_request(
            prompt, max_new_tokens, self.cfg.max_seq_len
        )
        s = bucket_len(len(prompt), maximum=self.cfg.max_seq_len)
        tokens = np.zeros((1, s), dtype=np.int32)
        tokens[0, : len(prompt)] = prompt
        true_len = np.array([len(prompt)], dtype=np.int32)
        max_cache = bucket_len(len(prompt) + max_new_tokens + 1,
                               maximum=self.cfg.max_seq_len)
        rng = jax.random.PRNGKey(seed)
        try:
            with self.mesh:
                cache = self.make_cache(1, max_cache)
                last_logits, cache = self._prefill_fn(
                    self.params, jnp.asarray(tokens), jnp.asarray(true_len),
                    cache, lora_idx,
                )
                cur = sample(last_logits, jax.random.fold_in(rng, 0),
                             sampling)
                for i in range(max_new_tokens):
                    tok = int(cur[0])
                    if tok == eos_id:
                        return
                    yield tok
                    if i == max_new_tokens - 1:
                        return
                    cur, cache = self._decode_fn(
                        self.params, cur[:, None], cache, rng, i + 1,
                        sampling, lora_idx,
                    )
        finally:
            if lease is not None:
                self.adapter_arena.release(lease)

    def model_info(self) -> dict:
        from ggrmcp_tpu.models import family_name

        return _model_info(self, family_name(self.cfg))


class EmbeddingEngine:
    """BERT-family embeddings: jitted, bucketed batch embed."""

    def __init__(
        self,
        cfg: bert_mod.BertConfig,
        serving: Optional[ServingConfig] = None,
        mesh: Optional[Mesh] = None,
        params=None,
        seed: int = 0,
    ):
        self.cfg = cfg
        self.serving = serving or ServingConfig()
        self.mesh = mesh if mesh is not None else mesh_mod.build_mesh(
            self.serving.mesh
        )
        if params is None:
            params = _sharded_init(
                partial(bert_mod.init_params, cfg=cfg),
                bert_mod.param_specs(cfg), self.mesh,
                jax.random.PRNGKey(seed),
            )
            logger.info(
                "initialized %s: %.1fM params",
                cfg.name, count_params(params) / 1e6,
            )
        else:
            params = _shard_params(params, bert_mod.param_specs(cfg), self.mesh)
        self.params = params
        # params as an explicit argument, not a capture (same compile-
        # cache/lowering rationale as DecoderEngine).
        self._embed_fn = jax.jit(self._embed_impl, static_argnums=(3,))
        # Memory ledger + compile watcher (same contract as
        # GenerationEngine._init_ledger; an embed sidecar's weights are
        # its one persistent allocation).
        from ggrmcp_tpu.serving import compile_watcher
        from ggrmcp_tpu.serving.memory_ledger import MemoryLedger

        obs = getattr(self.serving, "observability", None)
        enabled = bool(obs.enabled) if obs is not None else True
        self.ledger = MemoryLedger(enabled=enabled)
        self.ledger.register("weights", lambda: self.params)
        if enabled:
            compile_watcher.watcher.install()
            compile_watcher.watcher.mark_cold()

    def _embed_impl(self, params, tokens, mask, pooling: str):
        return bert_mod.embed(params, self.cfg, tokens, mask, pooling)

    MAX_CHUNK = 4096

    def embed(
        self,
        token_lists: list[list[int]],
        pooling: str = "mean",
        max_length: int = 0,
    ) -> np.ndarray:
        """Embed a batch of token lists; batches beyond MAX_CHUNK rows
        are processed in chunks and concatenated."""
        if len(token_lists) > self.MAX_CHUNK:
            parts = [
                self._embed_chunk(
                    token_lists[i : i + self.MAX_CHUNK], pooling, max_length
                )
                for i in range(0, len(token_lists), self.MAX_CHUNK)
            ]
            return np.concatenate(parts, axis=0)
        return self._embed_chunk(token_lists, pooling, max_length)

    def _embed_chunk(
        self, token_lists: list[list[int]], pooling: str, max_length: int
    ) -> np.ndarray:
        limit = max_length or self.cfg.max_seq_len
        b = len(token_lists)
        longest = min(max(len(t) for t in token_lists), limit)
        s = bucket_len(longest, maximum=self.cfg.max_seq_len)
        bb = bucket_len(b, minimum=1, maximum=self.MAX_CHUNK)
        tokens = np.zeros((bb, s), dtype=np.int32)
        mask = np.zeros((bb, s), dtype=np.int32)
        for i, ids in enumerate(token_lists):
            ids = ids[:limit]
            tokens[i, : len(ids)] = ids
            mask[i, : len(ids)] = 1
        with self.mesh:
            out = self._embed_fn(
                self.params, jnp.asarray(tokens), jnp.asarray(mask), pooling
            )
        return np.asarray(out)[:b]

    def model_info(self) -> dict:
        return _model_info(self, "bert")


def _model_info(engine, family: str) -> dict:
    sizes = dict(zip(engine.mesh.axis_names, engine.mesh.devices.shape))
    return {
        "model_id": engine.cfg.name,
        "family": family,
        "num_params_million": int(count_params(engine.params) / 1e6),
        "max_seq_len": engine.cfg.max_seq_len,
        "dtype": engine.cfg.dtype,
        "mesh": {k: v for k, v in sizes.items() if v > 1},
        "num_devices": int(engine.mesh.devices.size),
        "platform": engine.mesh.devices.flat[0].platform,
        "device_kind": engine.mesh.devices.flat[0].device_kind,
    }
