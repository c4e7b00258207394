"""Operations and bytes that one LFM2-MoE causal-LM training step *needs*,
computed from its shapes: the counts ``train_step_mfu.lfm2`` and
``short_conv_core_roofline`` divide by.

:func:`lfm2_clm_step`: matmul operations (2·m·n·k each) of what this chip
HOLDS and computes — the mixers and the dense layer whole, the held experts —
forward plus backward (3 × forward), nothing recomputed.  The routed experts
count at their EXPECTED share — ``tokens · num_experts_per_tok · held /
published`` rows — whatever the layer's implementation does with the rows
and however the router happens to split them.  Causal attention counts half
the square.  The convolutions, the gates, the norms, rotary and every other
elementwise pass are memory work and count in the bytes of no one: the byte
bound of the whole step is parameters and optimizer state only, a lower
bound, as in ``flops.py``.

:func:`short_conv_core`: the gated short convolutions of ONE training step
alone, operations and bytes from shapes, so that it reads the same whatever
implements the core.
"""
from .flops_solar import expert_params


def head_dim(c):
    return c["hidden_size"] // c["num_attention_heads"]


def layer_counts(c):
    """Layers by mixer (``conv`` / ``full_attention``) and by feed-forward
    (``dense`` / ``moe``)."""
    kinds = c["layer_types"]
    dense = c["num_dense_layers"]
    return {"conv": kinds.count("conv"), "full_attention": kinds.count("full_attention"),
            "dense": dense, "moe": len(kinds) - dense}


def conv_params(c):
    d = c["hidden_size"]
    return {"in": 3 * d * d, "conv": d * c["conv_L_cache"], "out": d * d}


def attention_params(c):
    d, hd = c["hidden_size"], head_dim(c)
    kv = c["num_key_value_heads"] * hd
    return {"q": d * d, "kv": 2 * kv * d, "o": d * d, "qk_norm": 2 * hd}


def dense_params(c):
    return 3 * c["hidden_size"] * c["intermediate_size"]


def moe_params(c):
    """Router over all the published experts, its selection bias, the held
    experts; no shared expert."""
    published = c["published"]["num_experts"]
    return published * (c["hidden_size"] + 1) + c["num_experts"] * expert_params(c)


def param_count(c):
    d, n = c["hidden_size"], layer_counts(c)
    return (n["conv"] * sum(conv_params(c).values())
            + n["full_attention"] * sum(attention_params(c).values())
            + n["dense"] * dense_params(c) + n["moe"] * moe_params(c)
            + c["num_hidden_layers"] * 2 * d        # the two norms of a layer
            + c["vocab_size"] * d + d)              # the tied embedding, the final norm


def macs_per_token(c):
    """Forward multiply-adds a token in weight matmuls, by part."""
    d, n = c["hidden_size"], layer_counts(c)
    published = c["published"]["num_experts"]
    conv, attn = conv_params(c), attention_params(c)
    return {
        "conv_projections": n["conv"] * (conv["in"] + conv["out"]),
        "attention_projections": n["full_attention"] * (attn["q"] + attn["kv"] + attn["o"]),
        "dense": n["dense"] * dense_params(c),
        "router": n["moe"] * published * d,
        "routed_experts": n["moe"] * c["num_experts_per_tok"] * c["num_experts"] / published
                          * expert_params(c),
        "head": c["vocab_size"] * d,
    }


def lfm2_clm_step(c, batch, seq):
    """Needed FLOPs and bytes of ONE training step on ``batch`` sequences of
    ``seq`` tokens (per program, i.e. over all its chips)."""
    tokens = batch * seq
    fwd = 2 * tokens * sum(macs_per_token(c).values())
    # causal scores and context: half of S x S, 2 x head_dim a query head
    fwd += (2 * batch * layer_counts(c)["full_attention"] * c["num_attention_heads"]
            * 2 * head_dim(c) * seq * seq / 2)
    # bf16 parameter read+write, fp32 master + two Adam moments read+write,
    # bf16 gradient write+read
    bytes_ = param_count(c) * (2 * 2 + 3 * 4 * 2 + 2 * 2)
    return {"flops": float(3 * fwd), "bytes": float(bytes_)}


def short_conv_core(c, batch, seq):
    """Needed FLOPs and bytes of the gated short convolutions of ONE training
    step: every conv layer's ``C ⊙ conv(B ⊙ x̃)``, forward × 3 with the
    backward.  Operations: the two gates and the taps' multiply-adds, ``2 +
    2 · K`` a channel and a token.  Bytes: B, C and x̃ read and the output
    written once a pass, each at 2 bytes (the projection's bf16 output and
    the core's, under the configuration's precision); from shapes alone."""
    d, layers = c["hidden_size"], layer_counts(c)["conv"]
    per_token = batch * seq * layers * d
    return {"flops": float(3 * per_token * (2 + 2 * c["conv_L_cache"])),
            "bytes": float(3 * per_token * 4 * 2)}
