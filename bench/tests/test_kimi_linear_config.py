"""The `kimi-linear-48b-a3b` configuration file against the program and
the catalog: the op streams it records are the ones `repro` traces today,
its model keys are the ones `repro.configs` traces, and its cell is the
deepseek-v2-lite-16b screen with only the configuration changed."""

import json

import pytest

from bench import check, harness

CONF = json.loads((harness.BENCH / "configs"
                   / "kimi-linear-48b-a3b.json").read_text())


@pytest.mark.parametrize("app", CONF["apps"])
def test_recorded_streams_are_the_traced_ones(app):
    from repro.core.multiapp import AppSpec
    spec = AppSpec.from_app(app, weight_peak_mode=CONF["weight_peak_mode"])
    assert check.stream_signature(spec) == CONF["streams"][app]


def test_model_keys_are_the_traced_architecture():
    from repro.configs import get_arch
    from repro.frontend.zoo import DECODE_CACHE, PREFILL_SEQ
    a = get_arch("kimi-linear-48b-a3b")
    m, e, k = a.mla, a.moe, a.kda
    lin = CONF["linear_attn_config"]
    assert (CONF["num_hidden_layers"], CONF["hidden_size"],
            CONF["num_attention_heads"], CONF["num_key_value_heads"],
            CONF["vocab_size"]) == (a.num_layers, a.d_model, a.num_heads,
                                    a.num_kv_heads, a.vocab_size)
    assert (CONF["kv_lora_rank"], CONF["qk_nope_head_dim"],
            CONF["qk_rope_head_dim"], CONF["v_head_dim"],
            CONF["q_lora_rank"] or 0, not CONF["mla_use_nope"]) == (
        m.kv_lora_rank, m.qk_nope_head_dim, m.qk_rope_head_dim,
        m.v_head_dim, m.q_lora_rank, m.use_rope)
    assert (lin["num_heads"], lin["head_dim"],
            lin["short_conv_kernel_size"], list(lin["kda_layers"])) == (
        k.num_heads, k.head_dim, k.conv_size, list(k.layers))
    plan = a.layer_plan()
    assert [i + 1 for i, (mixer, _) in enumerate(plan)
            if mixer == "mla"] == lin["full_attn_layers"]
    assert (CONF["num_experts"], CONF["num_experts_per_token"],
            CONF["moe_intermediate_size"], CONF["num_shared_experts"],
            CONF["moe_renormalize"], CONF["first_k_dense_replace"],
            CONF["intermediate_size"], CONF["routed_scaling_factor"]) == (
        e.num_experts, e.top_k, e.d_expert, e.num_shared,
        e.norm_topk_prob, e.first_dense, e.dense_d_ff, e.routed_scale)
    assert CONF["moe_router_activation_func"] == e.scoring == "sigmoid"
    assert CONF["num_expert_group"] == CONF["topk_group"] == 1
    assert CONF["rms_norm_eps"] == a.norm_eps
    assert (CONF["prefill_seq"], CONF["prefill_batch"],
            CONF["decode_cache"]) == (PREFILL_SEQ, 1, DECODE_CACHE)


def test_model_keys_are_the_catalog_config():
    """Every key of the catalog entry's `config` is at the top level, with
    its value (nested groups whole); none is reduced."""
    catalog = {
        "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 9216,
        "kv_lora_rank": 512,
        "linear_attn_config": {
            "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
            "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18,
                           19, 21, 22, 23, 25, 26],
            "num_heads": 32, "short_conv_kernel_size": 4},
        "mla_use_nope": True, "model_max_length": 1048576,
        "model_type": "kimi_linear", "moe_intermediate_size": 1024,
        "moe_layer_freq": 1, "moe_renormalize": True,
        "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
        "num_expert_group": 1, "num_experts": 256,
        "num_experts_per_token": 8, "num_hidden_layers": 27,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 0,
        "num_shared_experts": 1, "q_lora_rank": None,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
        "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
        "vocab_size": 163840}
    assert {key: CONF[key] for key in catalog} == catalog
    entry = next(c for c in harness.load_benchmark()["configs"]
                 if c["name"] == CONF["name"])
    assert entry["source"] == CONF["source"]
    assert entry["reduced"] == CONF["reduced"]
    assert not set(entry["reduced"]) & set(catalog)


def test_design_space_and_cell_are_the_deepseek_screen():
    deepseek = json.loads((harness.BENCH / "configs"
                           / "deepseek-v2-lite-16b.json").read_text())
    for key in ("domains", "hw", "area_budget", "weight_peak_mode"):
        assert CONF[key] == deepseek[key]
    cell = harness.load_cell("kimi-linear-48b-a3b.screen").traffic
    screen = harness.load_cell("deepseek-v2-lite-16b.screen").traffic
    drop = ("name", "config", "why")
    assert {k: v for k, v in cell.items() if k not in drop} == \
        {k: v for k, v in screen.items() if k not in drop}


def test_the_65k_screen_is_the_qwen_screen_at_a_larger_bucket():
    cell = harness.load_cell("qwen2.5-32b.screen-65k").traffic
    screen = harness.load_cell("qwen2.5-32b.screen").traffic
    assert (cell["engine_kwargs"], cell["max_rounds"]) == (
        {"batch": 65536}, 2)
    drop = ("name", "traffic", "why", "engine_kwargs", "max_rounds")
    assert {k: v for k, v in cell.items() if k not in drop} == \
        {k: v for k, v in screen.items() if k not in drop}
