"""The `deepseek-v2-lite-16b` configuration file against the program: the
op streams it records are the ones `repro` traces today, its model keys
are the ones `repro.configs` traces, and its cell is the qwen2.5-32b
screen with only the configuration (and one narrower limit) changed."""

import json

import pytest

from bench import check, harness

CONF = json.loads((harness.BENCH / "configs"
                   / "deepseek-v2-lite-16b.json").read_text())


@pytest.mark.parametrize("app", CONF["apps"])
def test_recorded_streams_are_the_traced_ones(app):
    from repro.core.multiapp import AppSpec
    spec = AppSpec.from_app(app, weight_peak_mode=CONF["weight_peak_mode"])
    assert check.stream_signature(spec) == CONF["streams"][app]


def test_model_keys_are_the_traced_architecture():
    from repro.configs import get_arch
    from repro.frontend.zoo import DECODE_CACHE, PREFILL_SEQ
    a = get_arch("deepseek-v2-lite-16b")
    m, e = a.mla, a.moe
    assert (CONF["num_hidden_layers"], CONF["hidden_size"],
            CONF["num_attention_heads"], CONF["num_key_value_heads"],
            CONF["vocab_size"]) == (a.num_layers, a.d_model, a.num_heads,
                                    a.num_kv_heads, a.vocab_size)
    assert (CONF["kv_lora_rank"], CONF["qk_nope_head_dim"],
            CONF["qk_rope_head_dim"], CONF["v_head_dim"],
            CONF["q_lora_rank"] or 0) == (
        m.kv_lora_rank, m.qk_nope_head_dim, m.qk_rope_head_dim,
        m.v_head_dim, m.q_lora_rank)
    assert (CONF["n_routed_experts"], CONF["num_experts_per_tok"],
            CONF["moe_intermediate_size"], CONF["n_shared_experts"],
            CONF["norm_topk_prob"], CONF["first_k_dense_replace"],
            CONF["intermediate_size"]) == (
        e.num_experts, e.top_k, e.d_expert, e.num_shared,
        e.norm_topk_prob, e.first_dense, e.dense_d_ff)
    assert CONF["scoring_func"] == "softmax"
    assert CONF["routed_scaling_factor"] == 1
    assert (CONF["rms_norm_eps"], CONF["rope_theta"]) == (a.norm_eps,
                                                          a.rope_theta)
    assert (CONF["prefill_seq"], CONF["prefill_batch"],
            CONF["decode_cache"]) == (PREFILL_SEQ, 1, DECODE_CACHE)


def test_design_space_and_cell_are_the_qwen_screens():
    qwen = json.loads((harness.BENCH / "configs"
                       / "qwen2.5-32b.json").read_text())
    for key in ("domains", "hw", "area_budget", "weight_peak_mode"):
        assert CONF[key] == qwen[key]
    cell = harness.load_cell("deepseek-v2-lite-16b.screen").traffic
    screen = harness.load_cell("qwen2.5-32b.screen").traffic
    drop = ("name", "config", "why", "limits")
    assert {k: v for k, v in cell.items() if k not in drop} == \
        {k: v for k, v in screen.items() if k not in drop}
    # the selection's float32 control read 1.66e-10 at one of nine seeds
    # here (PERF.md section 4), so its limit sits below that
    assert cell["limits"] == dict(screen["limits"], select_gap=1e-11)
