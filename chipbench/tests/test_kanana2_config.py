"""The Kanana-2-30B-A3B configuration holds one chip's share of the published
model: its leaf list is derived here from the model's ``config.json`` keys
and the stated cut, and the uncut model counts what the model card says."""
import json
import math

import ml_dtypes  # noqa: F401  names bfloat16 to numpy, as importing JAX does
import pytest

from chipbench import roofline
from chipbench.plan import REPO

#: kakaocorp/kanana-2-30b-a3b-instruct-2601 config.json, the keys that shape
#: the weights
PUBLISHED = {
    "num_hidden_layers": 48, "first_k_dense_replace": 1, "moe_layer_freq": 1,
    "hidden_size": 2048, "intermediate_size": 6144,
    "num_attention_heads": 32, "num_key_value_heads": 32,
    "q_lora_rank": None, "kv_lora_rank": 512,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "n_routed_experts": 128, "moe_intermediate_size": 768,
    "num_experts_per_tok": 6, "n_shared_experts": 2,
    "topk_method": "noaux_tc", "vocab_size": 128256,
    "tie_word_embeddings": False,
}
#: the deployment: a 16-chip aggregator; experts divided 16 ways, heads and
#: vocabulary 8 ways; layer 0 and 4 MoE layers held here
EXPERT_WAYS, HEAD_WAYS, VOCAB_WAYS, MOE_LAYERS_HELD = 16, 8, 8, 4


def _config():
    return json.loads((REPO / "chipbench" / "configs" / "kanana2-30b.json")
                      .read_text())


def kanana2_leaves(c, n_layers, experts, heads, vocab):
    """DeepSeek-V3 weights (MLA without q_lora, a noaux_tc router with its
    correction bias, stacked routed experts, the shared experts as one MLP)
    in JAX's (in, out) layout; ``experts``, ``heads`` and ``vocab`` are what
    one chip holds, the router keeps every expert."""
    assert c["q_lora_rank"] is None and c["moe_layer_freq"] == 1
    assert c["topk_method"] == "noaux_tc" and not c["tie_word_embeddings"]
    d, kv = c["hidden_size"], c["kv_lora_rank"]
    nope, rope, v = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"])
    f, shared = c["moe_intermediate_size"], c["moe_intermediate_size"] * c[
        "n_shared_experts"]
    out = [["embed", [vocab, d]]]
    for i in range(n_layers):
        p = f"layers.{i}."
        out += [[p + "input_layernorm", [d]],
                [p + "attn.q_proj", [d, heads * (nope + rope)]],
                [p + "attn.kv_a_proj_with_mqa", [d, kv + rope]],
                [p + "attn.kv_a_layernorm", [kv]],
                [p + "attn.kv_b_proj", [kv, heads * (nope + v)]],
                [p + "attn.o_proj", [heads * v, d]],
                [p + "post_attention_layernorm", [d]]]
        if i < c["first_k_dense_replace"]:
            w = c["intermediate_size"]
            out += [[p + "mlp.gate", [d, w]], [p + "mlp.up", [d, w]],
                    [p + "mlp.down", [w, d]]]
        else:
            e = c["n_routed_experts"]
            out += [[p + "moe.router", [d, e]], [p + "moe.router_bias", [e]],
                    [p + "moe.w_gate", [experts, d, f]],
                    [p + "moe.w_up", [experts, d, f]],
                    [p + "moe.w_down", [experts, f, d]],
                    [p + "moe.shared.gate", [d, shared]],
                    [p + "moe.shared.up", [d, shared]],
                    [p + "moe.shared.down", [shared, d]]]
    return out + [["norm", [d]], ["head", [d, vocab]]]


def _n_params(leaves):
    return sum(math.prod(s) for _, s in leaves)


def test_config_holds_one_chips_share():
    c = PUBLISHED
    want = kanana2_leaves(
        c, c["first_k_dense_replace"] + MOE_LAYERS_HELD,
        c["n_routed_experts"] // EXPERT_WAYS,
        c["num_attention_heads"] // HEAD_WAYS,
        c["vocab_size"] // VOCAB_WAYS)
    cfg = _config()
    assert cfg["leaves"] == want
    assert len(want) == cfg["n_leaves"] == 73
    assert _n_params(want) == roofline.n_params(cfg) == cfg["n_params"] \
        == 314_860_544
    assert cfg["dtype"] == "bfloat16" and cfg["algorithm"] == "fedavg"
    assert cfg["server_lr"] == 1.0 and cfg["limits"]["model_gap"] == 6e-3


def test_uncut_model_counts_the_model_card():
    """30B-A3B: 718 tensors, 30,670,815,104 parameters."""
    c = PUBLISHED
    leaves = kanana2_leaves(c, c["num_hidden_layers"], c["n_routed_experts"],
                            c["num_attention_heads"], c["vocab_size"])
    assert len(leaves) == 718
    assert _n_params(leaves) == 30_670_815_104


def test_reduced_keys_are_the_cut_and_nothing_else():
    """Every published key the file changes is in ``reduced``, with its
    published value beside it; no width is among them."""
    cfg = _config()
    changed = sorted(k for k, v in PUBLISHED.items() if cfg[k] != v)
    assert changed == sorted(cfg["reduced"])
    assert {k: cfg["published"][k] for k in changed} == {
        k: PUBLISHED[k] for k in changed}
    assert [cfg[k] for k in ("num_hidden_layers", "n_routed_experts",
                             "num_attention_heads", "num_key_value_heads",
                             "vocab_size")] == [5, 8, 4, 4, 16032]
    assert cfg["published"]["n_tensors"] == 718
    assert cfg["published"]["n_params"] == 30_670_815_104


@pytest.mark.parametrize("k", [10, 1])
def test_least_bytes_per_round_by_hand(k):
    """FedAvg publishes the mean: K bf16 updates read, the model written."""
    assert roofline.least_bytes_per_round(_config(), k) == (
        (k + 1) * 314_860_544 * 2)


def test_backlog_cell_resolves_to_its_files():
    """``kanana2-30b.backlog``: this configuration under the existing
    ``backlog`` mix, on every backlog metric's list but the harness's
    ``drain_host_ms_per_update.backlog``."""
    from chipbench import plan

    cell = plan.load_cell("kanana2-30b.backlog")
    mix = json.loads((REPO / "chipbench" / "mixes" / "backlog.json")
                     .read_text())
    assert cell.chips == 1 and cell.config == _config() and cell.mix == mix
    names = {m.name for m in cell.end_to_end + cell.per_layer}
    assert names == {"setup_s", "updates_per_s",
                     "launches_per_update.backlog", "agg_roofline.backlog",
                     "device_idle.backlog", "fold_host_ms_per_update.backlog",
                     "fold_self_ms_per_update.backlog",
                     "finish_host_ms_per_round.backlog",
                     "fold_device_ms_per_update.backlog"}
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    entry, = (c for c in bench["configs"] if c["name"] == "kanana2-30b")
    assert entry["source"] == _config()["source"]
    assert entry["reduced"] == _config()["reduced"]
