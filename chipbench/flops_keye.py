"""Operations and bytes that one Keye-VL-2.0 causal-LM training step *needs*,
computed from its shapes: the counts ``train_step_mfu.keye``,
``sparse_attention_core_roofline`` and ``indexer_select_roofline`` divide by.

:func:`keye_clm_step`: matmul operations (2·m·n·k each) of what this chip
HOLDS and computes, forward plus backward (3 × forward), nothing recomputed.
The routed experts count at their EXPECTED share — ``tokens ·
num_experts_per_tok · held / published`` rows — whatever the layer's
implementation does with the rows and however the router happens to split
them.  The attention core counts the SELECTED pairs only, ``4 · head_dim ·
heads · Σ_t min(t + 1, topk)`` a layer (scores and context): a program that
computes every causal block does more than is needed and reads low,
honestly.  The index scores count ``2 · index_dim · index_heads`` a causal
pair: the indexer has to score every visible key before it can choose.  The
selection itself, the head-averaged probabilities of the indexer's loss, the
norms, the rotary and every other elementwise pass are memory work and count
in the bytes of no one: the byte bound of the whole step is parameters and
optimizer state only, a lower bound, as in ``flops.py``.

:func:`sparse_attention_core` and :func:`indexer_select`: those two parts
alone over all layers, operations and bytes from shapes, so that each reads
the same whatever implements it.
"""


def attention_params(c):
    d, hd = c["hidden_size"], c["head_dim"]
    return d * hd * (2 * c["num_attention_heads"] + 2 * c["num_key_value_heads"])


def indexer_params(c):
    sa = c["sa_config"]
    return c["hidden_size"] * (sa["indexer_num_heads"] * sa["indexer_head_dim"]
                               + sa["indexer_head_dim"] + sa["indexer_num_heads"])


def expert_params(c):
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def macs_per_token(c):
    """Forward multiply-adds a token in weight matmuls, by part."""
    layers, published = c["num_hidden_layers"], c["published"]["num_experts"]
    return {
        "attention_projections": layers * attention_params(c),
        "indexer_projections": layers * indexer_params(c),
        "router": layers * published * c["hidden_size"],
        "routed_experts": layers * c["num_experts_per_tok"] * c["num_experts"] / published
                          * expert_params(c),
        "head": c["vocab_size"] * c["hidden_size"],
    }


def param_count(c):
    d, sa = c["hidden_size"], c["sa_config"]
    layer = (attention_params(c) + 2 * c["head_dim"]              # the two head norms
             + indexer_params(c) + 2 * sa["indexer_head_dim"]     # the LayerNorm
             + c["published"]["num_experts"] * d                  # the router, whole
             + c["num_experts"] * expert_params(c) + 2 * d)       # held experts, two norms
    return c["num_hidden_layers"] * layer + 2 * c["vocab_size"] * d + d


def selected_pairs(c, seq):
    """``Σ_t min(t + 1, topk)``: the (query, key) pairs a sequence selects."""
    k = min(c["sa_config"]["topk"], seq)
    return k * (k + 1) // 2 + (seq - k) * k


def core_flops(c, batch, seq):
    """One layer's attention core over the selected pairs, forward."""
    return 4 * c["head_dim"] * c["num_attention_heads"] * batch * selected_pairs(c, seq)


def index_flops(c, batch, seq):
    """One layer's index scores over the causal pairs, forward."""
    sa = c["sa_config"]
    return 2 * sa["indexer_head_dim"] * sa["indexer_num_heads"] * batch * seq * (seq + 1) // 2


def keye_clm_step(c, batch, seq):
    """Needed FLOPs and bytes of ONE training step on ``batch`` sequences of
    ``seq`` tokens (per program, i.e. over all its chips)."""
    fwd = 2 * batch * seq * sum(macs_per_token(c).values())
    fwd += c["num_hidden_layers"] * (core_flops(c, batch, seq) + index_flops(c, batch, seq))
    # bf16 parameter read+write, fp32 master + two Adam moments read+write,
    # bf16 gradient write+read
    bytes_ = param_count(c) * (2 * 2 + 3 * 4 * 2 + 2 * 2)
    return {"flops": float(3 * fwd), "bytes": float(bytes_)}


def sparse_attention_core(c, batch, seq, itemsize=2):
    """Needed FLOPs and bytes of every layer's attention core in ONE training
    step, forward × 3 with the backward: the selected pairs' scores and
    context, and q, k, v read and o written once (``itemsize`` 2: bf16 under
    AMP)."""
    hd, layers = c["head_dim"], c["num_hidden_layers"]
    elements = batch * seq * hd * 2 * (c["num_attention_heads"] + c["num_key_value_heads"])
    return {"flops": float(3 * layers * core_flops(c, batch, seq)),
            "bytes": float(3 * layers * elements * itemsize)}


def indexer_select(c, batch, seq):
    """Needed FLOPs and bytes of every layer's index scores and selection in
    ONE training step: the scores forward × 3 with the backward; q_I, k_I and
    w read (float32) in each of the three passes and ``topk`` int32 indices a
    row written once."""
    sa, layers = c["sa_config"], c["num_hidden_layers"]
    reads = batch * seq * (sa["indexer_num_heads"] * sa["indexer_head_dim"]
                           + sa["indexer_head_dim"] + sa["indexer_num_heads"]) * 4
    writes = batch * seq * min(sa["topk"], seq) * 4
    return {"flops": float(3 * layers * index_flops(c, batch, seq)),
            "bytes": float(layers * (3 * reads + writes))}
