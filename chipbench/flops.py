"""Operations and bytes a program *needs*, computed from its shapes.

Matmul operations only (2·m·n·k each), forward plus backward, nothing
recomputed: the count a roofline or an MFU divides by.  Bytes are the least
traffic the step cannot avoid: every parameter and optimizer slot read and
written once.  Activations are left out of the bytes, so the byte bound is a
lower bound and the step's share of it an upper bound.
"""


def bert_encoder_params(c):
    """Parameters of the matmul weights of one encoder layer."""
    h, i = c["hidden_size"], c["intermediate_size"]
    return 4 * h * h + 2 * h * i


def bert_pretrain_step(c, batch, seq, masked):
    """Needed FLOPs and bytes of ONE BERT pretraining step (forward and
    backward) on ``batch`` sequences of ``seq`` tokens with ``masked``
    predicted positions each.  ``c`` is the configuration file's mapping."""
    h, layers, vocab = c["hidden_size"], c["num_hidden_layers"], c["vocab_size"]
    tokens = batch * seq
    # dense layers: 2 FLOPs per parameter per token, forward
    fwd = 2 * tokens * layers * bert_encoder_params(c)
    # attention scores and context: QK^T and PV, 2*S*h each per token
    fwd += 2 * tokens * layers * 2 * seq * h
    # MLM head on the masked positions: transform h*h, decoder h*vocab
    fwd += 2 * batch * masked * (h * h + h * vocab)
    # pooler + NSP on one position per sequence
    fwd += 2 * batch * (h * h + 2 * h)
    flops = 3 * fwd  # backward = 2x forward (dgrad + wgrad)
    n_params = bert_param_count(c)
    # bf16 parameter read+write, fp32 master + two Adam moments read+write,
    # bf16 gradient write+read
    bytes_ = n_params * (2 * 2 + 3 * 4 * 2 + 2 * 2)
    return {"flops": float(flops), "bytes": float(bytes_)}


def bert_param_count(c):
    h, layers, vocab = c["hidden_size"], c["num_hidden_layers"], c["vocab_size"]
    i = c["intermediate_size"]
    per_layer = bert_encoder_params(c) + 4 * h + 2 * h + i + h + 4 * h
    embed = (vocab + c["max_position_embeddings"] + c["type_vocab_size"]) * h + 2 * h
    heads = h * h + h + 2 * h + h * vocab + vocab + h * h + h + 2 * h + 2
    return layers * per_layer + embed + heads
