"""The main path's kernels and programs compile for the v5e — asked of the
TPU compiler itself, for a chip that is described and not attached.

Interpret mode cannot show what these show: a slice that is not aligned to
the tiling, too much fast memory in a kernel, a Mosaic kernel the
partitioner is asked to split. A compile that passes is not a chip run:
nothing here executes, so nothing here says a result is right or fast.

This file holds the kernels, the train step and the two serve cells without
``layer_kinds`` (and the latent one); each serve configuration with kinds of
state has a file of its own, ``test_chip_compile_<model>.py``, and
``tests/chip_compile.py`` holds what they share.
"""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest

from chip_compile import (_float32_rows_a_choice, _live, _on,  # noqa: F401
                          one_chip, topo)
from ray_tpu.models import CONFIGS


def _qkv(shape, sharding):
    return [jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=sharding)] * 3


def test_flash_forward_1b_shape(one_chip):
    from ray_tpu.ops.attention import flash_attention

    compiled = jax.jit(lambda q, k, v: flash_attention(q, k, v, True)).lower(
        *_qkv((4, 2048, 16, 128), one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("shape", [
    (8, 1024, 16, 64),     # 350m
    (4, 2048, 16, 128),    # 1b
], ids=["hd64", "hd128"])
def test_flash_forward_and_pallas_backward(one_chip, shape):
    """The pallas backward by the rule, at both head sizes."""
    from ray_tpu.ops.attention import flash_attention

    def loss(q, k, v):
        return flash_attention(q, k, v, True).astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *_qkv(shape, one_chip)).compile()
    # the forward, the dq kernel and the dk/dv kernel
    assert compiled.as_text().count("tpu_custom_call") >= 3


def _custom_calls(text):
    """The instruction each custom call of a compiled text is:
    ``%jvp_flash_fwd_.1``."""
    return [line.split(" = ")[0] for line in text.splitlines()
            if "tpu_custom_call" in line]


def _flash_grads(q, k, v):
    from ray_tpu.ops.attention import flash_attention

    return jax.grad(lambda *a: flash_attention(*a, True).astype(
        jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("shape,backward", [
    ((4, 2048, 32, 64), True),     # cell 1: b4 x 2048, 32 heads of 64
    ((2, 2048, 16, 128), True),    # cell 4, one chip's rows (head_dim 128)
    ((1, 512, 32, 64), True),      # cell 1's gradient check
    ((1, 256, 16, 128), False),    # the engine's prefill, cell 3's bucket
    ((1, 128, 16, 128), False),    # ... and cell 5's: one block
], ids=["cell1", "cell4-a-chip", "cell1-check", "engine-256", "engine-128"])
def test_flash_kernels_at_the_cells_own_shapes(one_chip, shape, backward):
    """Each kernel is one custom call under its own name, at the blocks the
    kernels choose for the shape."""
    from ray_tpu.ops.attention import flash_attention

    fn = _flash_grads if backward else (
        lambda q, k, v: flash_attention(q, k, v, True))
    text = jax.jit(fn).lower(*_qkv(shape, one_chip)).compile().as_text()
    names = ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"][0 if backward
                                                           else 2:]
    made = _custom_calls(text)
    assert sorted(n for m in made for n in names if n in m) == names
    assert len(made) == len(names)


@pytest.mark.parametrize("shape", [(1, 8192, 32, 64), (1, 12288, 16, 128)],
                         ids=["64-at-8k", "128-at-the-bound"])
def test_flash_kernels_fast_memory(one_chip, shape):
    """``smollm2-1.7b.train-8k``'s shape (b1 x 8192, 32 heads of 64), and the
    longest sequence the backward's rule admits in bfloat16 at head_dim 128
    (``ops/attention.py:_use_pallas_bwd``; two heads of 64 share a program's
    128 lanes, and the test below compiles their bound): the kernels keep
    whole-sequence K/V (and Q/dO in dk/dv) blocks in fast memory. Forward and
    the pallas backward compile under the compiler's own limit, and what each
    needs of it is printed (``-s``): the least whole MiB of scoped VMEM it
    compiles under."""
    import sys

    from ray_tpu.ops.attention import flash_attention

    mod = sys.modules[flash_attention.__module__]
    B, S, H, D = shape
    assert mod._use_pallas_bwd(D, S)
    assert mod._use_pallas_bwd(D, S + 512) is (S < 12288)
    q, k, v = _qkv(shape, one_chip)
    text = jax.jit(_flash_grads).lower(q, k, v).compile().as_text()
    assert text.count("tpu_custom_call") == 3

    # the entries a differentiated call takes: at head_dim 64 the
    # projections' own layout [B, S, H x D], two heads and two rows of
    # statistics to a program (PR 47)
    heads = mod._heads_a_program(H, D)
    lse = jax.ShapeDtypeStruct((B * H // heads, heads, S), jnp.float32,
                               sharding=one_chip)
    forward = (lambda *a: mod._flash_fwd_two_heads(
        *a, True, False, *mod._blocks(S))) if heads == 2 else (
            lambda *a: mod._flash_fwd_impl(*a, True, False))
    alone = {
        "flash_fwd": (forward, (q, k, v)),
        "flash_bwd_dq": (lambda *a: mod.flash_attention_bwd(*a, True)[0],
                         (q, k, v, q, lse, q)),
        "flash_bwd_dkv": (lambda *a: mod.flash_attention_bwd(*a, True)[1:],
                          (q, k, v, q, lse, q)),
    }
    default_mib = 16    # the v5e compiler's scoped limit

    def fits(fn, args, mib):
        try:
            jax.jit(fn).lower(*args).compile(compiler_options={
                "xla_tpu_scoped_vmem_limit_kib": mib * 1024})
        except Exception as e:
            assert "vmem" in str(e), e
            return False
        return True

    need = {}
    for name, (fn, args) in alone.items():
        lo, hi = 0, default_mib     # (does not fit, fits]
        assert fits(fn, args, hi), name
        while hi - lo > 1:
            mid = (lo + hi) // 2
            lo, hi = (lo, mid) if fits(fn, args, mid) else (mid, hi)
        need[name] = hi
    print(f"flash kernels at bf16{list(shape)}: scoped VMEM needed, MiB of "
          f"{default_mib}: {need}")
    assert max(need.values()) <= default_mib


def _pair(S, H, D, dtype, sharding):
    """The backward pair alone, compiled for the chip."""
    import sys

    from ray_tpu.ops.attention import flash_attention

    mod = sys.modules[flash_attention.__module__]
    x = jax.ShapeDtypeStruct((1, S, H, D), dtype, sharding=sharding)
    heads = mod._heads_a_program(H, D)
    lse = jax.ShapeDtypeStruct((H // heads, heads, S), jnp.float32,
                               sharding=sharding)
    return jax.jit(lambda *a: mod.flash_attention_bwd(*a, True)).lower(
        x, x, x, x, lse, x).compile()


# (head_dim, dtype, the longest sequence the rule admits, one a little further
# at which the pair no longer compiles at 16 heads)
BOUNDS = [(64, jnp.bfloat16, 10240, 10752), (128, jnp.bfloat16, 12288, 13312),
          (256, jnp.bfloat16, 4096, 5120), (128, jnp.float32, 4096, 6144),
          (256, jnp.float32, 1536, 2560)]


@pytest.mark.parametrize("D,dtype,longest,too_long", BOUNDS, ids=[
    "64", "128", "256", "128-float32", "256-float32"])
def test_flash_backward_rule_stops_where_the_pair_still_compiles(
        one_chip, D, dtype, longest, too_long):
    """The rule's bound on the sequence, a tier for each width of a row in
    fast memory: at the longest sequence it admits the pair compiles, with
    few heads and with many (XLA lays a small operand out otherwise: at 2 to
    4 heads even 16,384 positions of head_dim 128 compile), and a little
    further it does not, which is why the bound is there."""
    import sys

    from ray_tpu.ops.attention import flash_attention

    mod = sys.modules[flash_attention.__module__]
    itemsize = jnp.dtype(dtype).itemsize
    assert mod._use_pallas_bwd(D, longest, itemsize)
    assert not mod._use_pallas_bwd(D, longest + 512, itemsize)
    for heads in (16, 64):
        _pair(longest, heads, D, dtype, one_chip)
    with pytest.raises(Exception, match="vmem"):
        _pair(too_long, 16, D, dtype, one_chip)


def test_flash_backward_past_the_bound_takes_the_fallback(one_chip):
    """Past the rule's bound a gradient still compiles: at head_dim 128 the
    next multiple of 512 after 12,288 positions holds the forward kernel
    alone (the backward is ``reference_attention``'s, S x S arrays in HBM:
    two heads here; at a cell's 16 to 32 heads they would not fit the
    chip)."""
    from ray_tpu.ops.attention import flash_attention

    text = jax.jit(jax.value_and_grad(
        lambda *a: flash_attention(*a, True).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))).lower(
            *_qkv((1, 12288 + 512, 2, 128), one_chip)).compile().as_text()
    made = _custom_calls(text)
    assert len(made) == 1 and "flash_fwd" in made[0]


def test_train_step_hands_the_flash_kernels_the_projections_layout(topo):
    """Cell 1's own train step (``benchmarks/configs/smollm2-1.7b.json``: 32
    heads of 64, b4 x 2048; depth 2), compiled for the described chip: three
    Mosaic calls a layer under their names, each on ``[4, 2048, 32 x 64]`` as
    the projections produce it, and no array in the ``(B*H, S, D)`` layout
    anywhere in the step: nothing brings a head's rows together (PR 47; the
    parent's step had 64 copies and 16 converts of such arrays in 8 layers).
    XLA still keeps q, k, v and their cotangents in a layout of its own around
    RoPE (positions along the lanes) and copies once between that and a call:
    those copies are dense ``[4, 2048, 2048]`` arrays and are counted here,
    at most one for each array the calls read or write."""
    layers = 2
    bundle, cfg, batch = _cell_bundle(topo, "smollm2-1.7b", layers)
    rows, S = batch["tokens"].shape
    text = bundle._fused_step.lower(
        _sds(bundle._abstract_params, bundle.param_shardings),
        _sds(bundle._abstract_opt, bundle.opt_shardings),
        batch).compile().as_text()
    H, D = cfg.n_heads, cfg.head_dim
    assert (rows, S, H, D) == (4, 2048, 32, 64)
    made = _custom_calls(text)
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert sum(name in m for m in made) == layers, (name, made)
    assert len(made) == 3 * layers
    flat = rf"bf16\[{rows},{S},{H * D}\]"
    for line in text.splitlines():
        if "tpu_custom_call" in line:
            assert re.search(rf"= \(?{flat}", line), line[:200]
    gathered = re.findall(
        rf"\[(?:{rows},{H},{S},{D}|{rows * H},{S},{D})\]", text)
    assert not gathered, sorted(set(gathered))
    # q, k, v, o, dO, dq, dk, dv: XLA's own layout to the call's or back
    # (56 in cell 1's 8 layers, none of them for o; 16 at this depth)
    entry = text[text.index("ENTRY "):]
    copies = re.findall(rf"= \w+\[{rows},{S},{H * D}\]\S* copy\(", entry)
    assert len(copies) <= 8 * layers, len(copies)


# the sparse serve cell's widths (OLMoE-1B-7B): 16/16 heads of 128 with the
# q/k norm, 64 experts of width 1024, top-8 without renormalisation, bfloat16
SPARSE = dict(n_kv_heads=16, d_ff=1024, n_experts=64, experts_per_token=8,
              norm_topk_prob=False, qk_norm=True, norm_eps=1e-5,
              vocab_size=50304, param_dtype=jnp.bfloat16)


def _lower_engine(one_chip, n_layers, bucket, sparse=False, rows=None,
                  **widths):
    """The engine's two programs at 1b widths (GQA 16/8, head_dim 128: the
    serve cell's heads), lowered for the described chip at the smoke's and
    the serve cell's geometry: 8 slots x 2048, pages of 16. ``sparse``: the
    widths and geometry of the sparse cell instead, 16 slots x 1024.
    ``rows``: the prefill's batch, 1 as the engine calls it (one admitted
    request a call), none = every slot as the benchmark's check calls it."""
    import flax.linen as nn

    from ray_tpu.llm import model_runner as mr
    from ray_tpu.llm.config import EngineConfig
    from ray_tpu.models.transformer import Transformer

    e = EngineConfig(max_num_seqs=8, max_model_len=2048)
    # "auto" asks the attached backend, which is the CPU here: steer the
    # dispatch the way a TPU backend would
    cfg = dataclasses.replace(CONFIGS["1b"], n_layers=n_layers,
                              attention_impl="flash")
    if sparse:
        e = EngineConfig(max_num_seqs=16, max_model_len=1024)
        cfg = dataclasses.replace(cfg, **SPARSE)
    cfg = dataclasses.replace(cfg, **widths)
    params = _on(jax.eval_shape(lambda: nn.meta.unbox(Transformer(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))), one_chip)
    cache = _on(jax.eval_shape(
        lambda: mr.init_cache(cfg, e.num_pages, e.page_size)), one_chip)
    B, MP = e.max_num_seqs, e.pages_per_seq
    R = rows or B

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    active = jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=one_chip)
    return cache, {
        "decode_step": lambda: mr.decode_step.lower(
            params, cfg, cache, i32(B), i32(B), i32(B, MP), active),
        "prefill": lambda: mr.prefill.lower(
            params, cfg, cache, i32(R, bucket), i32(R), i32(R, MP))}


def test_engine_decode_and_prefill_1b_widths(one_chip):
    """Depth cut to 2; prefill must carry the flash kernel."""
    _, lower = _lower_engine(one_chip, n_layers=2, bucket=1024)
    decode = lower["decode_step"]().compile()
    assert decode.memory_analysis().temp_size_in_bytes < 16 << 30
    assert "tpu_custom_call" in lower["prefill"]().compile().as_text()


def test_expert_layer_programs_compile_at_published_widths(one_chip):
    """Decode (128 assignments over 64 groups) and prefill (16 x 128
    positions, 16,384 assignments) of the sparse cell, depth cut to 2: three
    grouped matmuls a layer, under the names the trace's metrics read, beside
    prefill's flash kernel."""
    _, lower = _lower_engine(one_chip, n_layers=2, bucket=128, sparse=True)
    decode = lower["decode_step"]().compile().as_text()
    assert decode.count("tpu_custom_call") == 6
    assert len(set(re.findall(r"%(moe_gmm_decode\S*) = bf16\[128,", decode))) == 6
    prefill = lower["prefill"]().compile().as_text()
    assert prefill.count("tpu_custom_call") == 8
    assert len(set(re.findall(r"%(moe_gmm_prefill\S*) = bf16\[16384,",
                              prefill))) == 6
    assert not _float32_rows_a_choice(prefill, 8, 2048)


# the dense serve cell's widths (InternLM2-1.8B) where they differ from "1b"
INTERNLM2 = dict(d_ff=8192, vocab_size=92544, tie_embeddings=False)


@pytest.mark.parametrize("sparse,rows", [(False, 1), (False, None),
                                         (True, 1), (True, None)],
                         ids=["internlm2-1x256", "internlm2-8x256",
                              "olmoe-1x128", "olmoe-16x128"])
def test_one_row_prefill_compiles_beside_the_padded_batch(one_chip, sparse,
                                                          rows):
    """The prefill the engine calls, one admitted request's row at its length
    bucket ([1, 256] at InternLM2 widths, [1, 128] at OLMoE widths), beside
    the every-slot batch the benchmark's check still calls; depth cut to 2.
    Both carry the flash kernel; the sparse one the same count of custom
    calls and the six grouped matmuls under the name the trace's metric
    reads, over 128 x 8 = 1,024 assignments where the batch has 16,384. What
    the compiler says an execution holds live is printed (``-s``), and fits."""
    bucket = 128 if sparse else 256
    widths = {} if sparse else INTERNLM2
    _, lower = _lower_engine(one_chip, n_layers=2, bucket=bucket,
                             sparse=sparse, rows=rows, **widths)
    compiled = lower["prefill"]().compile()
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    print(f"prefill sparse={sparse} rows={rows or 'all'} x {bucket}: "
          f"{live} bytes live")
    assert 0 < live < 16 << 30
    if sparse:
        assert text.count("tpu_custom_call") == 8
        assignments = (rows or 16) * bucket * 8
        assert len(set(re.findall(
            rf"%(moe_gmm_prefill\S*) = bf16\[{assignments},", text))) == 6
    else:
        assert text.count("tpu_custom_call") == 2  # flash_fwd, once a layer


@pytest.mark.parametrize("sparse", [False, True], ids=["dense", "sparse"])
@pytest.mark.parametrize("program", ["decode_step", "prefill"])
def test_engine_writes_the_kv_cache_in_place(one_chip, program, sparse):
    """The compiled program scatters the new rows into the donated cache and
    holds no copy of a layer of it: no ``dynamic-update-slice`` whose result
    is the cache, no array of a layer's shape out of a fusion or a copy, and
    both caches aliased to the outputs. Depth 4, so that one cache (134 MB)
    is past what the compiler would stage into fast memory whole, as the
    full-depth program's is. The sparse model's programs too: the routing
    load (a KB) comes back beside the pages, not in them."""
    cache, lower = _lower_engine(one_chip, n_layers=4, bucket=256,
                                 sparse=sparse)
    compiled = lower[program]().compile()
    k = cache["dense"].k
    L, NP, P, KVH, HD = k.shape
    made = re.findall(r"^\s*(?:ROOT )?%\S+ = (.*?) ([\w-]+)\(",
                      compiled.as_text(), re.M)
    whole = f"bf16[{L},{NP},{P},{KVH},{HD}]"
    flat = f"bf16[{L * NP * P},{KVH},{HD}]"  # the same buffer, bitcast
    layer = [f"bf16[{dims}]" for dims in (f"1,{NP},{P},{KVH},{HD}",
                                          f"{NP},{P},{KVH},{HD}",
                                          f"{NP * P},{KVH},{HD}")]
    assert not [res for res, op in made
                if op == "dynamic-update-slice" and whole in res]
    assert not [(op, res) for res, op in made
                if op in ("fusion", "copy") and any(s in res for s in layer)]
    assert sum(op == "scatter" and (whole in res or flat in res)
               for res, op in made) == 2 * L
    load = 0 if cache.moe_load is None else cache.moe_load.size * 4
    assert 0 <= compiled.memory_analysis().alias_size_in_bytes \
        - 2 * k.size * k.dtype.itemsize <= load


def _sds(tree, shardings):
    """``tree``'s shapes and dtypes on ``shardings``, leaf by leaf."""
    return jax.tree_util.tree_map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        tree, shardings)


def _cell_bundle(topo, name, layers):
    """A train cell's own ``TrainStepBundle`` (the widths of
    ``benchmarks/configs/<name>.json``, its job block's mesh, optimizer and
    batch) at depth ``layers`` on the described chips, with the flash kernels
    a TPU backend would choose: (bundle, model configuration, batch)."""
    import json

    from benchmarks.jobs import common
    from benchmarks.registry import REPO

    with open(os.path.join(REPO, "benchmarks", "configs",
                           name + ".json")) as f:
        conf = json.load(f)
    conf["num_hidden_layers"] = layers
    job = conf["job"]
    cfg = dataclasses.replace(
        common.transformer_config(conf, job["seq_len"]),
        attention_impl="flash")
    bundle = common.build_bundle(cfg, job, topo.devices[:job["chips"]])
    rows = job["per_chip_batch"] * bundle.dp_size
    batch = {k: jax.ShapeDtypeStruct((rows, job["seq_len"]), dt,
                                     sharding=bundle.batch_sharding)
             for k, dt in (("tokens", jnp.int32), ("targets", jnp.int32),
                           ("mask", jnp.float32))}
    return bundle, cfg, batch


def test_sharded_update_step_partitions_over_four_chips(topo):
    """The data-parallel sharded-update step on a data=4 mesh of described
    chips, 1b widths (depth cut to 2): the flash kernel must survive the
    partitioner (it is wrapped in a shard_map; bare, the compiler refuses:
    "Mosaic kernels cannot be automatically partitioned") and the program
    must carry the grad reduce-scatter and the param all-gather."""
    from ray_tpu.parallel import TrainStepBundle, create_mesh, make_optimizer

    cfg = dataclasses.replace(CONFIGS["1b"], n_layers=2, max_seq_len=2048,
                              attention_impl="flash")
    mesh = create_mesh({"data": 4, "fsdp": 1, "seq": 1, "tensor": 1,
                        "expert": 1}, devices=topo.devices)
    bundle = TrainStepBundle(
        cfg, mesh, shard_update=True,
        optimizer_factory=lambda fn: make_optimizer(clip_spec_fn=fn))

    batch = {k: jax.ShapeDtypeStruct((4, 2048), dt,
                                     sharding=bundle.batch_sharding)
             for k, dt in (("tokens", jnp.int32), ("targets", jnp.int32),
                           ("mask", jnp.float32))}
    compiled = bundle._fused_step_sharded.lower(
        _sds(bundle._abstract_params, bundle.param_shardings),
        _sds(bundle._abstract_opt, bundle.opt_shard_shardings),
        batch).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "reduce-scatter" in text and "all-gather" in text


# -- what the sharded update sends between chips (cell 4's step, depth 2) --------


@pytest.fixture(scope="module")
def sharded_step_dp4(topo):
    """Cell 4's own ``TrainStepBundle`` (InternLM2's widths from
    ``benchmarks/configs/internlm2-1.8b-dp4.json``, its job block's mesh,
    optimizer and batch) at depth 2, its sharded step compiled for the four
    described chips: the collectives of the compiled text by the computation
    that holds them, and the compiler's memory analysis."""
    import collections

    bundle, _, batch = _cell_bundle(topo, "internlm2-1.8b-dp4", 2)
    compiled = bundle._fused_step_sharded.lower(
        _sds(bundle._abstract_params, bundle.param_shardings),
        _sds(bundle._abstract_opt, bundle.opt_shard_shardings),
        batch).compile()
    # {(computation, operation, dtype, dims): lines}. A fusion's body
    # (``fused_computation``) repeats the text of the collective its
    # ``async_collective_fusion`` holds: count by ENTRY and the latter.
    found = collections.Counter()
    where = None
    for line in compiled.as_text().splitlines():
        head = re.match(r"^(ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            where = "ENTRY" if head.group(1) else re.sub(
                r"[.\d]+$", "", head.group(2))
            continue
        op = re.match(r"^\s*(?:ROOT )?%\S+ = \(?(\w+)\[([\d,]*)\]\S* "
                      r"(all-gather|all-reduce)(?:-start)?\(", line)
        if op:
            dims = tuple(int(d) for d in op.group(2).split(",") if d)
            found[(where, op.group(3), op.group(1), dims)] += 1
    leaves = collections.Counter(
        tuple(x.shape) for x in jax.tree_util.tree_leaves(
            bundle._abstract_params) if x.ndim >= 2)
    return {"bundle": bundle, "found": found, "leaves": leaves,
            "memory": compiled.memory_analysis(), "batch": batch}


def test_sharded_step_gathers_parameters_in_bfloat16(sharded_step_dp4):
    """(a) Every all-gather whose result has the shape of a parameter
    matrix carries bfloat16, the dtype the model reads it in; none carries
    the float32 master."""
    found, leaves = sharded_step_dp4["found"], sharded_step_dp4["leaves"]
    dtypes = {dt for (_, op, dt, dims), _ in found.items()
              if op == "all-gather" and dims in leaves}
    assert dtypes == {"bf16"}, dtypes


def test_sharded_step_gathers_each_parameter_once(sharded_step_dp4):
    """(b) One gather a leaf and step: a cast left inside the rematerialised
    blocks gathers three times (forward, recomputation, backward)."""
    found, leaves = sharded_step_dp4["found"], sharded_step_dp4["leaves"]
    gathered = {}
    for (where, op, _, dims), n in found.items():
        if op == "all-gather" and dims in leaves and \
                where in ("ENTRY", "async_collective_fusion"):
            gathered[dims] = gathered.get(dims, 0) + n
    assert gathered == dict(leaves)


def test_sharded_step_keeps_the_gradients_reduce_scatters(sharded_step_dp4):
    """(c) The gradients stay reduce-scatters (XLA's ``all-reduce-scatter``
    fusions): no bare all-reduce of a matrix in ENTRY but the head's
    ``[2048, 92544]``, which the parent has too. A cotangent made
    replicated before it is sharded shows here as table-sized
    all-reduces."""
    found = sharded_step_dp4["found"]
    bare = {dims for (where, op, _, dims), _ in found.items()
            if where == "ENTRY" and op == "all-reduce" and len(dims) >= 2}
    assert bare <= {(2048, 92544)}, bare
    assert any(where.startswith("all-reduce-scatter")
               for (where, op, _, _) in found if op == "all-reduce")


def test_sharded_step_arguments_hold_a_quarter_of_the_master(sharded_step_dp4):
    """(d) The program's arguments hold a chip's quarter of the float32
    master beside its quarter of the moments: three quarters of the
    parameter tree less than the replicated layout's."""
    import numpy as np

    b, batch = sharded_step_dp4["bundle"], sharded_step_dp4["batch"]
    master = sum(int(np.prod(x.shape)) * 4 for x in
                 jax.tree_util.tree_leaves(b._abstract_params))
    per_chip_batch = sum(int(np.prod(v.shape)) * 4 for v in batch.values()) // 4
    replicated = master + b.opt_state_bytes_total() // 4 + per_chip_batch
    now = sharded_step_dp4["memory"].argument_size_in_bytes
    assert replicated - now == pytest.approx(0.75 * master, rel=0.01)


# -- the latent model's programs (Moonlight-16B-A3B, the benchmark's file) -------


def _lower_latent(one_chip, n_layers):
    """The engine's programs at the published widths of
    ``benchmarks/configs/moonlight-16b-a3b.json`` and its job block's geometry
    (32 slots x 8192, pages of 256), depth cut to ``n_layers`` (the dense
    layer and the sparse ones after it)."""
    import json

    import flax.linen as nn

    from benchmarks.jobs import common
    from benchmarks.registry import REPO
    from ray_tpu.llm import model_runner as mr
    from ray_tpu.llm.config import EngineConfig
    from ray_tpu.models.transformer import Transformer

    with open(os.path.join(REPO, "benchmarks", "configs",
                           "moonlight-16b-a3b.json")) as f:
        conf = json.load(f)
    e = EngineConfig(**conf["job"]["engine"])
    cfg = dataclasses.replace(
        common.transformer_config(conf, e.max_model_len), n_layers=n_layers,
        attention_impl="flash")
    params = _on(jax.eval_shape(lambda: nn.meta.unbox(Transformer(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))), one_chip)
    cache = _on(jax.eval_shape(
        lambda: mr.init_cache(cfg, e.num_pages, e.page_size)), one_chip)
    B, MP = e.max_num_seqs, e.pages_per_seq

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    active = jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=one_chip)

    def prefill(rows, bucket):
        return mr.prefill.lower(params, cfg, cache, i32(rows, bucket),
                                i32(rows), i32(rows, MP))

    return cache, prefill, lambda: mr.decode_step.lower(
        params, cfg, cache, i32(B), i32(B), i32(B, MP), active)


def test_latent_decode_compiles_with_the_mla_kernel(one_chip):
    """Decode at 32 slots, the dense layer and one sparse one: one
    ``mla_decode`` call a layer under the name the trace's metrics read,
    beside the three grouped matmuls; the latent rows are written in place
    (one scatter a layer into the whole array, the cache aliased) and no
    copy of the cache is made for the kernel: the temporaries stay far below
    one layer of it (336 MB). Depth 2 keeps the compile short: these run
    beside tests that time themselves."""
    cache, _, decode = _lower_latent(one_chip, n_layers=2)
    compiled = decode().compile()
    text = compiled.as_text()
    assert len(set(re.findall(r"%(mla_decode\S*) = bf16\[32,16,512\]",
                              text))) == 2
    assert len(set(re.findall(r"%(moe_gmm_decode\S*) = bf16\[192,",
                              text))) == 3
    assert text.count("tpu_custom_call") == 5
    live, temp = _live(compiled)
    rows = cache["latent"]
    L, NP, P, W = rows.shape
    print(f"latent decode, 2 layers, 32 slots: {live} bytes live, "
          f"{temp} of temporaries; cache {rows.size * 2}")
    assert (P, W) == (256, 640)
    assert temp < NP * P * W * 2 // 4
    assert compiled.memory_analysis().alias_size_in_bytes >= rows.size * 2


@pytest.mark.parametrize("rows,bucket", [(1, 512), (1, 8192)],
                         ids=["1x512", "1x8192"])
def test_latent_prefill_compiles_with_unequal_head_sizes(one_chip, rows,
                                                         bucket):
    """The engine's prefill at the mix's least and largest bucket, the dense
    layer and one sparse one: the flash kernel over 192-wide q . k and
    128-wide values (at 8192 positions its whole-sequence K/V need a
    fast-memory limit of their own, ``ops/attention.py``), the three grouped
    matmuls over rows x 6 assignments. What an execution holds live is
    printed (``-s``); the full depth and the check's [32, 512] batch are in
    PERF.md section 4."""
    _, prefill, _ = _lower_latent(one_chip, n_layers=2)
    compiled = prefill(rows, bucket).compile()
    text = compiled.as_text()
    assert len(set(re.findall(
        rf"%(flash_fwd\S*) = \(bf16\[{rows * 16},{bucket},128\]", text))) == 2
    assert len(set(re.findall(
        rf"%(moe_gmm_prefill\S*) = bf16\[{rows * bucket * 6},", text))) == 3
    assert text.count("tpu_custom_call") == 5
    assert not _float32_rows_a_choice(text, 6, 2048)
    live, temp = _live(compiled)
    print(f"latent prefill, 2 layers, [{rows}, {bucket}]: {live} bytes live, "
          f"{temp} of temporaries")
    assert 0 < live < 16 << 30
