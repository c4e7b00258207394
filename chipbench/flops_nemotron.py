"""Operations and bytes that one Nemotron-H causal-LM training step *needs*,
computed from its shapes: the counts ``train_step_mfu.nemotron`` and
``ssm_scan_roofline`` divide by.

:func:`nemotron_clm_step`: matmul operations (2·m·n·k each) of what this chip
HOLDS and computes, forward plus backward (3 × forward), nothing recomputed.
The routed experts count at their EXPECTED share — ``tokens ·
num_experts_per_tok · held / published`` rows — whatever the layer's
implementation does with the rows and however the router happens to split
them.  Causal attention counts half the square.  A Mamba-2 layer counts its
two projections and the recurrence itself at ``4·S·H·P·N`` (the state's
update ``Δ x̃ ⊗ B`` and its read-out ``h C``, 2·P·N each a head and a
position): what the step-by-step recurrence needs, which is LESS than what
the chunked form spends (its Q × Q blocks are the price of running on the
MXU).  The convolution, the gated norm and every other elementwise pass are
memory work and count in the bytes of no one: the byte bound of the whole
step is parameters and optimizer state only, a lower bound, as in
``flops.py``.

:func:`mamba2_scan`: ONE layer's scan alone, operations and bytes from shapes,
so that it reads the same whatever implements the scan.
"""


def mamba_widths(c):
    inner = c["mamba_num_heads"] * c["mamba_head_dim"]
    conv = inner + 2 * c["n_groups"] * c["ssm_state_size"]
    return inner, conv


def mamba_params(c):
    d, h = c["hidden_size"], c["mamba_num_heads"]
    inner, conv = mamba_widths(c)
    return {"in_proj": d * (inner + conv + h), "out_proj": inner * d,
            "conv": conv * (c["conv_kernel"] + 1), "per_head": 3 * h, "gate_norm": inner}


def attention_params(c):
    d, hd = c["hidden_size"], c["head_dim"]
    return d * hd * (2 * c["num_attention_heads"] + 2 * c["num_key_value_heads"])


def expert_params(c):
    return 2 * c["hidden_size"] * c["moe_intermediate_size"]


def shared_expert_params(c):
    return 2 * c["hidden_size"] * c["moe_shared_expert_intermediate_size"] * c["n_shared_experts"]


def layer_counts(c):
    pattern = c["hybrid_override_pattern"]
    return {kind: pattern.count(kind) for kind in "ME*"}


def macs_per_token(c):
    """Forward multiply-adds a token in weight matmuls, by part."""
    d, n = c["hidden_size"], layer_counts(c)
    published = c["published"]["n_routed_experts"]
    mamba = mamba_params(c)
    return {
        "mamba_projections": n["M"] * (mamba["in_proj"] + mamba["out_proj"]),
        "attention_projections": n["*"] * attention_params(c),
        "router": n["E"] * published * d,
        "shared_expert": n["E"] * shared_expert_params(c),
        "routed_experts": n["E"] * c["num_experts_per_tok"] * c["n_routed_experts"] / published
                          * expert_params(c),
        "head": c["vocab_size"] * d,
    }


def param_count(c):
    d, n = c["hidden_size"], layer_counts(c)
    moe = (c["published"]["n_routed_experts"] * (d + 1)
           + c["n_routed_experts"] * expert_params(c) + shared_expert_params(c))
    return (n["M"] * sum(mamba_params(c).values()) + n["*"] * attention_params(c)
            + n["E"] * moe + c["num_hidden_layers"] * d      # each layer's norm
            + 2 * c["vocab_size"] * d + d)


def scan_flops(c, batch, seq):
    """The recurrence of one Mamba-2 layer, forward: ``4·S·H·P·N``."""
    return 4 * batch * seq * c["mamba_num_heads"] * c["mamba_head_dim"] * c["ssm_state_size"]


def nemotron_clm_step(c, batch, seq):
    """Needed FLOPs and bytes of ONE training step on ``batch`` sequences of
    ``seq`` tokens (per program, i.e. over all its chips)."""
    tokens, n = batch * seq, layer_counts(c)
    fwd = 2 * tokens * sum(macs_per_token(c).values())
    # causal scores and context: half of S x S, 2 x head_dim a query head
    fwd += 2 * batch * n["*"] * c["num_attention_heads"] * 2 * c["head_dim"] * seq * seq / 2
    fwd += n["M"] * scan_flops(c, batch, seq)
    # bf16 parameter read+write, fp32 master + two Adam moments read+write,
    # bf16 gradient write+read
    bytes_ = param_count(c) * (2 * 2 + 3 * 4 * 2 + 2 * 2)
    return {"flops": float(3 * fwd), "bytes": float(bytes_)}


def mamba2_scan(c, batch, seq, itemsize=2):
    """Needed FLOPs and bytes of the scans of ONE training step: every
    Mamba-2 layer's, forward × 3 with the backward.  Bytes: x̃, B, C and Δ
    read and y written once (``itemsize`` 2: bf16 under AMP), from shapes
    alone."""
    h, p = c["mamba_num_heads"], c["mamba_head_dim"]
    gn = c["n_groups"] * c["ssm_state_size"]
    layers = layer_counts(c)["M"]
    elements = batch * seq * (2 * h * p + 2 * gn + h)     # x̃ and y, B and C, Δ
    return {"flops": float(3 * layers * scan_flops(c, batch, seq)),
            "bytes": float(3 * layers * elements * itemsize)}
