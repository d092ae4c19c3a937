"""LLM configs (reference: llm/_internal/serve/core/configs/llm_config.py:141).

``LLMConfig`` describes one deployable model: which transformer config to
instantiate (or checkpoint to load), the engine's batching/cache geometry,
and serve-level options. ``SamplingParams`` mirrors the per-request options
(reference: vLLM SamplingParams surfaced through ray.serve.llm).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Optional


@dataclass
class SamplingParams:
    max_tokens: int = 64
    temperature: float = 0.0
    top_k: int = 0  # 0 = no top-k restriction
    top_p: float = 1.0
    stop_token_ids: tuple = ()
    seed: Optional[int] = None

    def __post_init__(self):
        if self.max_tokens < 1:
            raise ValueError("max_tokens must be >= 1")
        if self.temperature < 0:
            raise ValueError("temperature must be >= 0")


@dataclass
class EngineConfig:
    """Cache/batching geometry of the JAX engine.

    The paged KV cache holds ``num_pages`` pages of ``page_size`` tokens per
    layer; a sequence owns ceil(len/page_size) pages recorded in its block
    table (vLLM's PagedAttention layout, re-done as fixed-shape jnp arrays so
    every decode step hits one compiled XLA program).
    """

    max_num_seqs: int = 8           # concurrent decode slots (batch size)
    max_model_len: int = 2048       # prompt + generation cap per sequence
    page_size: int = 16             # tokens per KV page
    num_pages: Optional[int] = None  # default: enough for all slots + scratch
    max_top_k: int = 64             # static top-k width compiled into sampler
    # a prompt is padded to the smallest power-of-two multiple of this that
    # holds it: its length bucket, one compiled program a bucket for a request
    # prefilled alone and one more for each number of rows (2, 4) that
    # requests admitted in one step share a call at (llm/engine.py:
    # prefill_groups)
    prefill_bucket_min: int = 32
    # routed experts per layer of the model this deployment serves (0: a
    # dense model). The engine refuses to start on a model with another
    # number: a lost override would otherwise serve the dense preset under
    # a sparse deployment's name, and a program that has no expert layer
    # refuses such a deployment where it is described, not in a replica.
    expect_experts: int = 0
    # the router's outputs where the deployment is one rank of an
    # expert-parallel group and holds only ``expect_experts`` of them (0: it
    # holds them all), checked against the model the same way
    expect_routed_experts: int = 0
    # the router's outputs that are no expert but the identity (0: every
    # output is an expert), checked against the model the same way
    expect_zero_experts: int = 0
    # width of the latent the cache holds a position (0: per-head K/V pages),
    # checked against the model as expect_experts is, and for its reason
    expect_latent_rank: int = 0
    # layers with recurrent state (a decoder-hybrid-decoder's Mamba layers, a
    # model's gated short convolutions, Mamba-2, delta-rule or power-retention layers; 0: none, every layer
    # keeps pages or a ring), checked the same way: such a model keeps rows by slot beside its
    # pages
    expect_state_layers: int = 0
    # taps of the model's gated short convolutions (0: it has none): such a
    # layer keeps ``taps - 1`` rows a slot, so like expect_latent_rank this is
    # the size of what the cache holds, checked against the model the same way
    expect_conv_taps: int = 0
    # heads of the model's Mamba-2 layers (0: it has none): such a layer
    # keeps a matrix state a head and slot, the largest thing the cache holds
    # a slot, checked against the model the same way
    expect_ssm_heads: int = 0
    # heads of the model's delta-rule linear-attention layers (0: it has
    # none): such a layer keeps a float32 matrix state a head and slot, checked
    # against the model the same way
    expect_kda_heads: int = 0
    # key/value heads of the model's power-retention layers (0: it has none):
    # such a layer keeps the symmetric square of each such head's keys
    # against its values a slot, float32 (34.6 MB a slot and layer at 8 heads
    # of 128): the largest thing any cache holds, checked against the model
    # the same way
    expect_retention_heads: int = 0
    # the model's Mamba-1 layers normalise the step size's low-rank input, B
    # and C (``TransformerConfig.ssm_inner_norms``: Jamba's), checked against
    # the model the same way: a lost override would serve a scan without its
    # three norms under the deployment's name
    expect_ssm_inner_norms: bool = False

    def __post_init__(self):
        if self.max_model_len % self.page_size:
            raise ValueError("max_model_len must be a multiple of page_size")
        if self.num_pages is None:
            # one scratch page (index 0) absorbs masked-out writes
            self.num_pages = 1 + self.max_num_seqs * self.pages_per_seq

    @property
    def pages_per_seq(self) -> int:
        return self.max_model_len // self.page_size


@dataclass
class LLMConfig:
    """One deployable LLM (reference: llm_config.py:141 model_loading_config
    + engine_kwargs + deployment_config)."""

    model_id: str = "tiny"           # key into models.transformer.CONFIGS
    checkpoint_path: Optional[str] = None  # msgpack params (orbax/flax) dir
    tokenizer: str = "byte"          # "byte" or a HF tokenizer name
    seed: int = 0                    # random-init weights + sampling stream
    engine_config: EngineConfig = field(default_factory=EngineConfig)
    # serve-level
    num_replicas: int = 1
    ray_actor_options: Dict[str, Any] = field(default_factory=dict)
    # forwarded to TransformerConfig (e.g. attention_impl for CI)
    model_overrides: Dict[str, Any] = field(default_factory=dict)

    def transformer_config(self):
        import dataclasses as _dc

        from ray_tpu.models.transformer import CONFIGS

        cfg = CONFIGS[self.model_id]
        if self.model_overrides:
            cfg = _dc.replace(cfg, **self.model_overrides)
        return cfg
