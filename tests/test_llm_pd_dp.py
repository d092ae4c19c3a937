"""LLM serving patterns: prefill/decode disaggregation + DP serving.

Reference:
llm/_internal/serve/serving_patterns/prefill_decode/pd_server.py:31 and
.../data_parallel/{dp_server.py:14,dp_rank_assigner.py} — CPU tier with the
tiny model (SURVEY.md §4: accelerator features need a hardware-free tier).
"""

import numpy as np
import pytest

import ray_tpu
from ray_tpu.llm.config import EngineConfig, LLMConfig, SamplingParams


def make_config(**ekw):
    eng = dict(max_num_seqs=4, max_model_len=128, page_size=16,
               prefill_bucket_min=16)
    eng.update(ekw)
    return LLMConfig(model_id="tiny", engine_config=EngineConfig(**eng),
                     model_overrides={"attention_impl": "xla"})


def test_kv_export_import_matches_monolithic():
    """Greedy completion via prefill-engine -> KV hand-off -> decode-engine
    must equal the monolithic engine's output exactly."""
    from ray_tpu.llm.engine import JaxLLMEngine

    mono = JaxLLMEngine(make_config(), seed=0)
    prompt = "the quick brown fox jumps"
    expect = mono.generate([prompt], SamplingParams(max_tokens=10))[0]

    prefill_eng = JaxLLMEngine(make_config(), seed=0)
    # decode engine shares weights (same seed) as a real deployment would
    decode_eng = JaxLLMEngine(make_config(), seed=0)
    state = prefill_eng.prefill_only(
        "r1", prompt, SamplingParams(max_tokens=10))
    assert state["generated"], "prefill must emit the first token"
    assert state["dense"].k.shape[0] == mono.mcfg.n_layers
    # prefill engine released its slot/pages
    assert prefill_eng.num_active() == 0
    decode_eng.add_request_with_kv(state)
    done = None
    while done is None:
        for out in decode_eng.step():
            if out.finished:
                done = out
    assert done.token_ids == expect.token_ids
    assert done.finish_reason == expect.finish_reason


def test_prefill_only_single_token_request():
    from ray_tpu.llm.engine import JaxLLMEngine

    eng = JaxLLMEngine(make_config(), seed=0)
    state = eng.prefill_only("r1", "hello", SamplingParams(max_tokens=1))
    assert state["finished"] and state["finish_reason"] == "length"
    assert len(state["generated"]) == 1


@pytest.fixture
def cluster():
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4)
    yield ray_tpu
    ray_tpu.shutdown()


def test_pd_actors_end_to_end(cluster):
    """Prefill replica + decode replica serve a completion end-to-end
    (the round-2 verdict's done criterion)."""
    import cloudpickle

    from ray_tpu.llm.engine import JaxLLMEngine
    from ray_tpu.llm.pd import DecodeWorker, PrefillWorker

    cfg = make_config()
    mono = JaxLLMEngine(cfg, seed=0)
    prompt = "hello distributed serving"
    expect = mono.generate([prompt], SamplingParams(max_tokens=8))[0]
    blob = cloudpickle.dumps(mono.params)

    p = ray_tpu.remote(num_cpus=0.5)(PrefillWorker).remote(cfg, blob)
    d = ray_tpu.remote(num_cpus=0.5)(DecodeWorker).remote(cfg, blob)
    state = ray_tpu.get(
        p.prefill.remote(prompt, SamplingParams(max_tokens=8)), timeout=300)
    out = ray_tpu.get(d.decode.remote(state), timeout=300)
    assert out["token_ids"] == expect.token_ids
    assert out["finish_reason"] == expect.finish_reason
    # division of labor: prefill engine never decoded, decode never prefilled
    pm = ray_tpu.get(p.metrics.remote(), timeout=60)
    dm = ray_tpu.get(d.metrics.remote(), timeout=60)
    assert pm["prefill_tokens"] > 0 and pm["decode_steps"] == 0
    assert dm["decode_steps"] > 0 and dm["prefill_tokens"] == 0


def test_dp_replicas_get_distinct_ranks_and_spread(cluster):
    """Router spreads completions across 2 DP engine replicas, each holding
    a distinct dp rank."""
    from ray_tpu.llm.pd import build_dp_openai_app

    handle = build_dp_openai_app(make_config(), dp_size=2)
    seen_ranks = set()
    for i in range(8):
        out = ray_tpu.get(handle.remote({"prompt": f"ping {i}",
                                         "max_tokens": 2}), timeout=300)
        assert out["choices"][0]["text"] is not None
        seen_ranks.add(out["dp_rank"])
    assert seen_ranks == {0, 1}, f"router did not spread: {seen_ranks}"
