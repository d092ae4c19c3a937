"""LLM layer: TPU-native continuous-batching inference engine + serving.

Reference: python/ray/llm — LLMConfig (llm/_internal/serve/core/configs/
llm_config.py:141), vLLM engine wrapper (engines/vllm/vllm_engine.py),
OpenAI-compatible ingress, and batch-inference processors over Data
(llm/_internal/batch/processor/). The TPU-native redesign replaces the vLLM
CUDA engine with a JAX engine: paged KV cache in HBM, a one-request prefill
per length bucket and a single-token decode step, each compiled once, and
continuous batching in a host-side scheduler.

Heavy modules (jax) load lazily: importing ``ray_tpu.llm`` must stay cheap
for workers that only route requests.
"""

from ray_tpu.llm.config import LLMConfig, SamplingParams


def __getattr__(name):
    if name in ("JaxLLMEngine",):
        from ray_tpu.llm.engine import JaxLLMEngine

        return JaxLLMEngine
    if name in ("build_llm_deployment", "build_openai_app", "LLMServer"):
        from ray_tpu.llm import serve_llm

        return getattr(serve_llm, name)
    if name in ("build_llm_processor",):
        from ray_tpu.llm.data_llm import build_llm_processor

        return build_llm_processor
    raise AttributeError(name)


__all__ = [
    "LLMConfig",
    "SamplingParams",
    "JaxLLMEngine",
    "build_llm_deployment",
    "build_openai_app",
    "build_llm_processor",
]
