"""``benchmarks/architectures/phi4flash.py`` reached the way the harness reaches
it (through the resolver, from the committed configuration file), against
counts made by hand from the published shapes, and its plain reference
against the properties the equations promise (no program is imported: the
program is held to this reference in ``tests/test_hybrid.py``)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import registry
from benchmarks.registry import HERE, REPO, Cell
from benchmarks.trace import reduce

CELL = "phi-4-mini-flash-reasoning.mathturns-saturated-b48"
D, F, V, INNER = 2560, 10240, 200064, 5120
MLP = 3 * D * F + 4 * D                   # with the layer's two LayerNorms
MAMBA = D * 2 * INNER + INNER * 192 + 160 * INNER + INNER * D + INNER * 23
ATTN = D * 5120 + 5120 + D * D + D + 6 * 64
CROSS = D * D + D + D * D + D + 6 * 64
GMU = 2 * D * INNER
TOTAL = 9 * MAMBA + 9 * ATTN + 7 * GMU + 7 * CROSS + 32 * MLP + V * D + 2 * D


@pytest.fixture(scope="module")
def cell():
    return Cell(CELL, os.path.join(REPO, "BENCHMARK.json"))


@pytest.fixture(scope="module")
def arch(cell):
    return cell.architecture()


def test_the_module_has_the_eight_members_and_imports_no_program(arch):
    assert all(callable(getattr(arch, m)) for m in registry.MEMBERS)
    with open(arch.__file__) as f:
        source = f.read()
    assert "import ray_tpu" not in source and "from ray_tpu" not in source


def test_the_configuration_keeps_every_published_width(cell):
    c = cell.config
    assert (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["intermediate_size"], c["vocab_size"],
            c["num_hidden_layers"], c["sliding_window"], c["mb_per_layer"],
            c["tie_word_embeddings"], c["layer_norm_eps"]) == (
        2560, 40, 20, 10240, 200064, 32, 512, 2, True, 1e-5)
    entry = {e["name"]: e for e in cell.benchmark["configs"]}[c["name"]]
    assert entry["reduced"] == list(c["reduced"]) == ["max_position_embeddings"]
    cut = c["reduced"]["max_position_embeddings"]
    assert cut["to"] == c["max_position_embeddings"] < cut["from"] == 262144
    assert c["job"]["engine"]["max_model_len"] == c["max_position_embeddings"]
    for key in ("mamba_d_state", "mamba_d_conv", "mamba_expand", "torch_dtype",
                "initializer", "page_size"):
        assert key in c["assumed"], key
    assert c["departures"] and c["stands_for"]


def test_total_params_by_hand(cell, arch):
    assert arch.total_params(cell.config) == TOTAL == 3_852_562_944
    assert arch.layer_kinds(32) == (("mamba", "window") * 8 + ("mamba", "full")
                                    + ("gmu", "cross") * 7)
    # a token multiplies by every matrix of every layer and by the table
    mats = (9 * (MAMBA - INNER * 23) + 9 * (D * 5120 + D * D) + 7 * GMU
            + 7 * 2 * D * D + 32 * 3 * D * F + V * D)
    assert arch.active_matmul_params(cell.config) == mats


def test_kernel_costs_count_the_least_the_mix_allows(cell, arch):
    c = cell.config
    ops, nbytes = arch.kernel_cost("paged_gqa_decode", c, {"max_num_seqs": 48})
    assert nbytes == 48 * 256 * 5120            # k and v of 20 heads of 64, bf16
    assert ops == 48 * 256 * 40 * 2 * (64 + 128)
    assert arch.kernel_cost("window_gqa_decode", c, {}) == (ops, nbytes)
    ops, nbytes = arch.kernel_cost("ssm_scan", c, {})
    assert ops == 256 * INNER * 16 * 7
    assert nbytes == 256 * (3 * INNER + 2 * 16) * 4
    with pytest.raises(KeyError):
        arch.kernel_cost("flash_fwd", c, {})


def test_every_new_metric_reads_through_the_cell(cell):
    new = ["paged_gqa_decode_roofline", "attn.shared_decode_dev_ms",
           "window_gqa_decode_roofline", "window.decode_attn_dev_ms",
           "ssm_scan_roofline", "ssm.scan_dev_ms", "ssm.step_dev_ms",
           "attn.live_tokens_per_step", "attn.read_per_live",
           "prefill.cross_rows_share"]
    entries = {m["name"]: m for m in cell.per_layer()}
    for name in new:
        with open(os.path.join(HERE, "layer_metrics", name + ".json")) as f:
            raw = json.load(f)
        assert raw["reduce"] in reduce.REDUCTIONS and cell.reader(name) == raw
        assert entries[name]["workloads"] == [CELL]
        assert entries[name]["moves"] == "serve_tokens_per_s"
    window = {"shared_kv_live_tokens": 1000 * 130000, "decode_steps": 1000,
              "shared_kv_read_tokens": 1000 * 136500, "prefill_cross_rows": 90,
              "prefill_batch_tokens": 90 * 4096, "generated_tokens": 48000}
    ctx = {"trace": None, "spans": {}, "counters": window, "facts": {}}
    got = {k: v["value"] for k, v in cell.per_layer_values(ctx).items()}
    assert got["attn.live_tokens_per_step"] == 130000
    assert got["attn.read_per_live"] == 1.05
    assert got["prefill.cross_rows_share"] == 1 / 4096
    # the parent's engine has no such counters: a ratio whose denominator is
    # missing is left out, one whose numerator is missing reads 0; none raises
    ctx["counters"] = {"decode_steps": 10, "prefill_batch_tokens": 512}
    got = cell.per_layer_values(ctx)
    assert "attn.read_per_live" not in got
    assert got["prefill.cross_rows_share"]["value"] == 0


@pytest.fixture(scope="module")
def tiny(arch):
    """A small model under the reference's own parameter names, drawn here."""
    rng = np.random.default_rng(0)
    d, H, KVH, hd, f, inner, N, R, K = 32, 8, 4, 4, 48, 64, 16, 2, 4
    w = lambda *s: jnp.asarray(rng.normal(size=s) * 0.2, jnp.float32)  # noqa: E731
    layers = []
    for i, kind in enumerate(arch.layer_kinds(8)):
        lp = {"kind": kind, "ln1": 1 + w(d), "ln1_bias": w(d), "ln2": 1 + w(d),
              "ln2_bias": w(d), "gate_proj": w(d, f), "up_proj": w(d, f),
              "down_proj": w(f, d)}
        if kind == "mamba":
            lp.update(in_proj=w(d, 2 * inner), conv_weight=w(K, inner),
                      conv_bias=w(inner), x_proj=w(inner, R + 2 * N) * 3,
                      dt_proj=w(R, inner), dt_bias=w(inner) - 2.0,
                      A_log=jnp.log(jnp.broadcast_to(
                          jnp.arange(1.0, N + 1), (inner, N))),
                      D=1 + w(inner), out_proj=w(inner, d))
        elif kind == "gmu":
            lp.update(in_proj=w(d, inner), out_proj=w(inner, d))
        else:
            if kind == "cross":
                lp.update(Wq=w(d, H * hd) * 3, Wq_bias=w(H * hd))
            else:
                lp.update(Wqkv=w(d, (H + 2 * KVH) * hd) * 3,
                          Wqkv_bias=w((H + 2 * KVH) * hd))
            lp.update(out_proj=w(H * hd, d), out_bias=w(d), subln=1 + w(2 * hd),
                      **{n: w(hd) for n in ("lambda_q1", "lambda_k1",
                                            "lambda_q2", "lambda_k2")})
        layers.append(lp)
    params = {"embed_tokens": w(64, d), "norm": 1 + w(d), "norm_bias": w(d),
              "layers": layers}
    rcfg = {"num_attention_heads": H, "num_key_value_heads": KVH,
            "layer_norm_eps": 1e-5, "sliding_window": 4, "ssm_state": N,
            "dt_rank": R}
    return params, rcfg


def test_reference_is_causal_and_reads_beyond_the_window_only_through_state(
        arch, tiny):
    """A later token changes no earlier logits; an early token still reaches
    the last position (through the recurrent state and the full layer) though
    it lies outside every window; ``last`` slices and does not recompute."""
    params, rcfg = tiny
    toks = np.random.default_rng(1).integers(0, 64, (1, 20))
    full = arch.forward(params, jnp.asarray(toks), rcfg)
    assert full.shape == (1, 20, 64)
    other = toks.copy()
    other[0, 15] = (other[0, 15] + 1) % 64
    moved = arch.forward(params, jnp.asarray(other), rcfg)
    np.testing.assert_allclose(moved[:, :15], full[:, :15], atol=1e-5)
    assert float(jnp.abs(moved[:, 15:] - full[:, 15:]).max()) > 1e-3
    early = toks.copy()
    early[0, 0] = (early[0, 0] + 1) % 64
    assert float(jnp.abs(arch.forward(params, jnp.asarray(early), rcfg)[:, -1]
                         - full[:, -1]).max()) > 1e-4
    np.testing.assert_allclose(
        arch.forward(params, jnp.asarray(toks), rcfg, last=3), full[:, -3:],
        atol=1e-6)
    loss = arch.loss(params, jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:]),
                     rcfg)
    assert np.isfinite(float(loss)) and float(loss) > 0


def test_reference_mamba_is_the_recurrence_written_out(arch, tiny):
    """The reference's scan against the same recurrence in numpy, a position
    and a channel at a time."""
    params, rcfg = tiny
    lp = params["layers"][0]
    h = jnp.asarray(np.random.default_rng(2).normal(size=(1, 6, 32)), jnp.float32)
    out, y = arch.mamba(h, lp, rcfg)
    az = np.asarray(h[0] @ lp["in_proj"])
    a_raw, z = az[:, :64], az[:, 64:]
    w, b = np.asarray(lp["conv_weight"]), np.asarray(lp["conv_bias"])
    silu = lambda t: t / (1 + np.exp(-t))   # noqa: E731
    a = np.stack([silu(sum(w[k] * (a_raw[t - 3 + k] if t - 3 + k >= 0 else 0)
                           for k in range(4)) + b) for t in range(6)])
    x = a @ np.asarray(lp["x_proj"])
    dt = np.log1p(np.exp(x[:, :2] @ np.asarray(lp["dt_proj"])
                         + np.asarray(lp["dt_bias"])))
    A = -np.exp(np.asarray(lp["A_log"]))
    s = np.zeros((64, 16))
    want = []
    for t in range(6):
        s = np.exp(dt[t][:, None] * A) * s \
            + (dt[t] * a[t])[:, None] * x[t, 2:18][None]
        want.append(s @ x[t, 18:] + np.asarray(lp["D"]) * a[t])
    np.testing.assert_allclose(np.asarray(y[0]), np.stack(want), rtol=2e-4,
                               atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(out[0]), (np.stack(want) * silu(z)) @ np.asarray(lp["out_proj"]),
        rtol=2e-4, atol=2e-5)
