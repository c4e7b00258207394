"""Operations and bytes that one Xing4.0 causal-LM training step *needs*,
computed from its shapes: the count ``train_step_mfu`` divides by.

Matmul operations only (2·m·n·k each), forward plus backward (3 × forward),
nothing recomputed: of what this chip HOLDS and computes.  The routed
experts count at their EXPECTED share — ``tokens · num_experts_per_tok ·
held / published`` rows — whatever the layer's implementation does with the
rows and however the router happens to split them.  Causal attention counts
half the square.  The residual mixes count their coefficient projection
(``W_hc``); their elementwise passes over the state are memory work and
count in the bytes of no one: the byte bound below is parameters and
optimizer state only, a lower bound, as in ``flops.py``.
"""


def attention_params(c):
    d, h = c["hidden_size"], c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return (d * c["q_lora_rank"] + c["q_lora_rank"] * h * qk
            + d * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
            + c["kv_lora_rank"] * h * (c["qk_nope_head_dim"] + c["v_head_dim"])
            + h * c["v_head_dim"] * d)


def expert_params(c):
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def mix_params(c):
    """One sublayer's coefficient projection."""
    n = c["hc_mult"]
    return (n * n + 2 * n) * n * c["hidden_size"]


def layer_counts(c):
    dense = c["first_k_dense_replace"]
    return dense, c["num_hidden_layers"] - dense


def macs_per_token(c):
    """Forward multiply-adds a token in weight matmuls, by part."""
    d = c["hidden_size"]
    dense, sparse = layer_counts(c)
    held_share = c["n_routed_experts"] / c["published"]["n_routed_experts"]
    return {
        "attention_projections": c["num_hidden_layers"] * attention_params(c),
        "residual_mix": 2 * c["num_hidden_layers"] * mix_params(c),
        "dense_mlp": dense * 3 * d * c["intermediate_size"],
        "router": sparse * c["published"]["n_routed_experts"] * d,
        "shared_expert": sparse * c["n_shared_experts"] * expert_params(c),
        "routed_experts": sparse * c["num_experts_per_tok"] * held_share * expert_params(c),
        "head": c["vocab_size"] * d,
    }


def param_count(c):
    d = c["hidden_size"]
    dense, sparse = layer_counts(c)
    n = c["hc_mult"]
    per_layer = (attention_params(c) + c["q_lora_rank"] + c["kv_lora_rank"]   # + the two inner norms
                 + 2 * (mix_params(c) + 3 + n * n + 2 * n) + 2 * d)
    moe = (c["published"]["n_routed_experts"] * (d + 1)
           + (c["n_routed_experts"] + c["n_shared_experts"]) * expert_params(c))
    return (c["num_hidden_layers"] * per_layer + dense * 3 * d * c["intermediate_size"]
            + sparse * moe + 2 * c["vocab_size"] * d + d)


def xing4_clm_step(c, batch, seq):
    """Needed FLOPs and bytes of ONE training step on ``batch`` sequences of
    ``seq`` tokens (per program, i.e. over all its chips)."""
    tokens = batch * seq
    fwd = 2 * tokens * sum(macs_per_token(c).values())
    # causal scores and context: half of S x S, (d_qk + d_v) a head
    h = c["num_attention_heads"]
    per_pair = c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"]
    fwd += 2 * batch * c["num_hidden_layers"] * h * per_pair * seq * seq / 2
    # bf16 parameter read+write, fp32 master + two Adam moments read+write,
    # bf16 gradient write+read
    bytes_ = param_count(c) * (2 * 2 + 3 * 4 * 2 + 2 * 2)
    return {"flops": float(3 * fwd), "bytes": float(bytes_)}
