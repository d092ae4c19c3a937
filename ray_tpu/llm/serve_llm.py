"""Serve-LLM: LLMServer deployments + OpenAI-style app builder.

Reference: llm/_internal/serve/core/server/llm_server.py (LLMServer
deployment wrapping an engine), build_openai_app (OpenAI-compatible
ingress). Each replica owns one ``JaxLLMEngine``; requests are enqueued to
the engine and a single pump task drives ``engine.step()`` while anything is
unfinished, so concurrent requests continuously batch on the TPU. A call of
``step()`` dispatches its programs and returns the outputs of the call before
it: the pump's hop through the event loop, the answers it builds and the
engine's next admission all run while the device works, and
``has_unfinished()`` keeps the pump calling until the last step is read.

Prefix-aware routing (reference: routing_policies/prefix_aware/): the
``LLMHandle`` hashes a prompt prefix to prefer a consistent replica, which
keeps likely-shared KV prefixes on the same engine.
"""

from __future__ import annotations

import asyncio
import uuid
from typing import Any, Dict, List, Optional

import ray_tpu
from ray_tpu.llm.config import LLMConfig, SamplingParams
from ray_tpu.serve import api as serve_api


class LLMServer:
    """Deployment callable owning one engine (reference: llm_server.py)."""

    def __init__(self, config: LLMConfig, params_blob: Optional[bytes] = None):
        from ray_tpu.llm.engine import JaxLLMEngine

        params = None
        if params_blob is not None:
            # driver-authored params blob: deserialize only through the
            # audited serialization boundary (raylint SER001)
            from ray_tpu._private.serialization import loads_trusted

            params = loads_trusted(params_blob)
        self.config = config
        self.engine = JaxLLMEngine(config, params=params, seed=config.seed)
        self._futures: Dict[str, asyncio.Future] = {}
        self._pump_task: Optional[asyncio.Task] = None

    async def _pump(self):
        loop = asyncio.get_event_loop()
        try:
            while self.engine.has_unfinished():
                outputs = await loop.run_in_executor(None, self.engine.step)
                for out in outputs:
                    if out.finished and out.request_id in self._futures:
                        toks = [t for t in out.token_ids
                                if t != self.engine.tokenizer.eos_token_id]
                        # build the answer BEFORE the future leaves the
                        # table: if this raises, the handler below must
                        # still find the request and fail it, not orphan it
                        result = {"token_ids": out.token_ids,
                                  "text": self.engine.tokenizer.decode(toks),
                                  "finish_reason": out.finish_reason}
                        fut = self._futures.pop(out.request_id)
                        if not fut.done():
                            fut.set_result(result)
                await asyncio.sleep(0)
        except Exception as e:
            # fail every pending request rather than hanging its caller. An
            # abort reads the step in flight, which may be what raised: it
            # is dropped unread first, so every slot and page comes back and
            # this exception is the one raised
            self.engine.discard_in_flight()
            for rid, fut in list(self._futures.items()):
                if not fut.done():
                    fut.set_exception(RuntimeError(f"engine step failed: {e}"))
                self.engine.abort_request(rid)
            self._futures.clear()
            raise
        finally:
            self._pump_task = None

    async def _submit(self, prompt: Any, params: SamplingParams) -> dict:
        rid = uuid.uuid4().hex
        fut = asyncio.get_event_loop().create_future()
        self._futures[rid] = fut
        self.engine.add_request(rid, prompt, params)
        if self._pump_task is None or self._pump_task.done():
            self._pump_task = asyncio.ensure_future(self._pump())
        return await fut

    async def completions(self, prompt: str, *, max_tokens: int = 64,
                          temperature: float = 0.0, top_k: int = 0,
                          top_p: float = 1.0) -> dict:
        params = SamplingParams(max_tokens=max_tokens, temperature=temperature,
                                top_k=top_k, top_p=top_p)
        return await self._submit(prompt, params)

    async def chat(self, messages: List[dict], **kw) -> dict:
        prompt = "".join(
            f"<{m.get('role', 'user')}>{m.get('content', '')}" for m in messages
        ) + "<assistant>"
        return await self.completions(prompt, **kw)

    async def __call__(self, body: dict) -> dict:
        """OpenAI-ish JSON entry point (used by the HTTP proxy)."""
        kw = {k: body[k] for k in ("max_tokens", "temperature", "top_k", "top_p")
              if k in body}
        if "messages" in body:
            out = await self.chat(body["messages"], **kw)
            choice = {"message": {"role": "assistant",
                                  "content": out["text"]}}
            kind = "chat.completion"
        else:
            out = await self.completions(body.get("prompt", ""), **kw)
            choice = {"text": out["text"]}
            kind = "text_completion"
        choice.update(index=0, token_ids=out["token_ids"],
                      finish_reason=out["finish_reason"])
        return {"id": uuid.uuid4().hex, "object": kind, "choices": [choice],
                "usage": {"completion_tokens": len(out["token_ids"])}}

    async def update_weights(self, store_name: str,
                             version: Optional[int] = None) -> dict:
        """Live weight update from the weight plane: pull ``version``
        (default: newest) from the named WeightStore and swap engine params
        between steps. In-flight requests keep decoding — the swap is one
        attribute assignment on the pump's thread boundary, so no request
        is dropped or restarted (a step already dispatched keeps the tree it
        was given). Rolled out across replicas with
        ``handle.broadcast("update_weights", store_name)``."""
        loop = asyncio.get_event_loop()

        def _pull():
            from ray_tpu.weights import WeightStore

            return WeightStore(store_name).pull(version, return_version=True)

        tree, ver = await loop.run_in_executor(None, _pull)
        self.engine.params = tree
        return {"version": ver, "model_id": self.config.model_id}

    async def save_engine_state(self, path: str, *, step: int = 0) -> dict:
        """Checkpoint the engine params through the checkpoint plane
        (``ray_tpu/ckpt``): ``path`` becomes a manifest + chunk store, so
        rolling saves across replicas dedup identical params to the same
        chunks. Runs off-loop — in-flight requests keep decoding."""
        loop = asyncio.get_event_loop()

        def _save():
            from ray_tpu.llm.engine import save_params

            return save_params(self.engine.params, path, step=step)

        manifest_path = await loop.run_in_executor(None, _save)
        return {"manifest": manifest_path, "model_id": self.config.model_id}

    async def load_engine_state(self, path: str) -> dict:
        """Swap engine params from a checkpoint-plane store (or legacy
        msgpack dir); the swap is one attribute assignment between steps,
        like ``update_weights``."""
        loop = asyncio.get_event_loop()

        def _load():
            from ray_tpu.llm.engine import _load_params

            return _load_params(path)

        self.engine.params = await loop.run_in_executor(None, _load)
        return {"model_id": self.config.model_id, "source": path}

    def engine_metrics(self) -> dict:
        return dict(self.engine.metrics)

    def device_info(self) -> dict:
        """The device, its memory high-water mark and the compile cache as
        THIS replica process sees them — the replica is the only process of
        a serve app that may touch the chip, so these facts cannot be read
        from the driver."""
        from ray_tpu.utils import device_facts

        return device_facts()


def build_llm_deployment(config: LLMConfig, params: Any = None,
                         name: Optional[str] = None) -> serve_api.Application:
    """Deployment app for one LLMConfig (reference: build_llm_deployment)."""
    opts = dict(config.ray_actor_options) or {"num_cpus": 1.0}
    params_blob = None
    if params is not None:
        import cloudpickle

        params_blob = cloudpickle.dumps(params)
    dep = serve_api.deployment(
        LLMServer, name=name or f"llm:{config.model_id}",
        num_replicas=config.num_replicas,
        max_ongoing_requests=config.engine_config.max_num_seqs * 2,
        ray_actor_options=opts)
    return dep.bind(config, params_blob)


def build_openai_app(configs: List[LLMConfig], params: Any = None
                     ) -> Dict[str, serve_api.DeploymentHandle]:
    """Deploy one LLMServer per config; returns name->handle (the HTTP proxy
    then serves POST /<name> with OpenAI-style bodies)."""
    handles = {}
    for cfg in configs:
        app = build_llm_deployment(cfg, params=params)
        handles[app.deployment.name] = serve_api.run(app)
    return handles


class LLMHandle:
    """Prefix-aware handle: same prompt prefix -> same replica when healthy,
    keeping likely-shared KV prefixes on one engine. Thin veneer over the
    first-class ``routing_policy="prefix"`` handle policy
    (ray_tpu/serve/autoscale/router.py — consistent-hash ring, so replica
    churn remaps only ~1/N of the prefix space; hit/miss counters land on
    ``ray_tpu.serve.prefix_cache_*``)."""

    def __init__(self, deployment_name: str, prefix_len: int = 64):
        self._inner = serve_api.DeploymentHandle(
            deployment_name, routing_policy="prefix")
        self._inner._router().prefix_len = prefix_len

    def remote(self, body: dict):
        return self._inner.remote(body)
