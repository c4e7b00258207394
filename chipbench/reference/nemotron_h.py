"""Plain reference of Nemotron-H's forward pass and causal-LM loss.

Straightforward ``jax.numpy`` in float32 with matmul precision "highest": no
kernels, no chunks, no sorting of routed pairs, no recomputation, no AMP.
The state-space scan is the step-by-step recurrence (``lax.scan`` over
time), the convolution four shifted adds, attention has its keys and values
repeated over the group, experts are a loop over the experts held, each
applied to every token and weighted by a gate that is zero where the token
did not choose it.  Imports nothing of the program's model code; it reads
the program's parameters by name.

Equations (per token ``x`` [d]; every layer is ``x + f(RMSNorm(x))`` with
``f`` given by the layer's character in ``hybrid_override_pattern``):

``M`` (Mamba-2; H heads of P, G groups of N, inner width H·P):
    [z | xBC | dt] = W_in x
    xBC_t = silu(Σ_k w[:, k] · xBC_{t−3+k} + b)           (zeros before t = 0)
    [x̃ | B | C] = xBC;   Δ = max(softplus(dt + dt_bias), time_step_floor)
    h_t = exp(Δ_t A) h_{t−1} + Δ_t · x̃_t ⊗ B_t,   A = −exp(A_log),  h_0 = 0
    y_t = h_t C_t + D · x̃_t                  (head h reads group h // (H/G))
    out = W_out RMSNorm_grouped(y · silu(z))   (statistics over each group's H·P/G)
``*`` (attention): causal softmax((q·k) · head_dim^-½) v on ``num_attention_heads``
    query heads and ``num_key_value_heads`` key/value heads; no bias, no rotary.
``E`` (experts): s = σ(W_r x); the k largest s + b_sel; gates
    ``scaling · s_i / Σ_selected s_j``; ``y = Σ_held g_i E_i(x) + E_shared(x)``,
    every expert ``W_down relu(W_up x)²``.

Departures from the published model, each also under ``assumed`` in
``chipbench/configs/nemotron-3-nano-30b-a3b.json``:

* no rotary embedding on the attention layers (``rope_theta`` and
  ``partial_rotary_factor`` are in ``config.json`` and unused: the
  ``nemotron_h`` modeling file applies none);
* the mixer's inner width is ``mamba_num_heads · mamba_head_dim`` (``expand``
  is not used for it);
* q, k and v projections are stored as one weight (q rows, then k, then v),
  the routed experts' as ``[count, d, width]`` and ``[count, width, d]``;
* experts held elsewhere add nothing (``experts_held``): the partial sum is
  what goes on to the next layer, as it does in the program.
"""
import jax
import jax.numpy as jnp

from .common import by_suffix


def rms_norm(x, gamma, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gamma


def relu2(x, w_up, w_down):
    """Weights ``[out, in]``."""
    return jnp.square(jax.nn.relu(x @ w_up.T)) @ w_down.T


def causal_conv(x, w, b):
    """``x`` [B, S, C], ``w`` [C, K], ``b`` [C]: tap K−1 is the position itself."""
    s, taps = x.shape[1], w.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(padded[:, k:k + s] * w[:, k] for k in range(taps)) + b


def recurrence(x, delta, a, b, c):
    """``h_t = exp(Δ_t a) h_{t−1} + Δ_t x_t ⊗ B_t``; ``y_t = h_t C_t``, step by
    step.  ``x`` [B, S, H, P]; ``delta`` [B, S, H]; ``a`` [H]; ``b``, ``c``
    [B, S, H, N] (already one a head)."""
    def step(h, t):
        x_t, d_t, b_t, c_t = t
        h = (jnp.exp(d_t * a)[..., None, None] * h
             + (d_t[..., None] * x_t)[..., :, None] * b_t[..., None, :])
        return h, jnp.einsum("bhpn,bhn->bhp", h, c_t)

    h0 = jnp.zeros(x.shape[:1] + x.shape[2:] + b.shape[-1:], x.dtype)
    time_major = [t.swapaxes(0, 1) for t in (x, delta, b, c)]
    return jax.lax.scan(step, h0, time_major)[1].swapaxes(0, 1)


def mamba(p, pre, x, c):
    bsz, s, _ = x.shape
    h, hd, g, n = (c["mamba_num_heads"], c["mamba_head_dim"], c["n_groups"],
                   c["ssm_state_size"])
    inner = h * hd
    zxbcdt = x @ p[pre + "in_proj_weight"].T
    z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * g * n], axis=-1)
    xbc = jax.nn.silu(causal_conv(xbc, p[pre + "conv_weight"], p[pre + "conv_bias"]))
    xs, b, cm = jnp.split(xbc, [inner, inner + g * n], axis=-1)
    xs = xs.reshape(bsz, s, h, hd)
    per_head = lambda m: jnp.repeat(m.reshape(bsz, s, g, n), h // g, axis=2)
    delta = jnp.maximum(jax.nn.softplus(dt + p[pre + "dt_bias"]), c["time_step_floor"])
    y = recurrence(xs, delta, -jnp.exp(p[pre + "A_log"]), per_head(b), per_head(cm))
    y = (y + p[pre + "D"][:, None] * xs).reshape(bsz, s, inner)
    y = (y * jax.nn.silu(z)).reshape(bsz, s, g, inner // g)
    y = y / jnp.sqrt(jnp.mean(y * y, -1, keepdims=True) + c["layer_norm_epsilon"])
    return (y.reshape(bsz, s, inner) * p[pre + "norm_gamma"]) @ p[pre + "out_proj_weight"].T


def attention(p, pre, x, c, query_block):
    bsz, s, _ = x.shape
    h, h_kv, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    qkv = x @ p[pre + "qkv_weight"].T
    q, k, v = jnp.split(qkv, [h * hd, (h + h_kv) * hd], axis=-1)
    q = q.reshape(bsz, s, h, hd)
    k = jnp.repeat(k.reshape(bsz, s, h_kv, hd), h // h_kv, axis=2)
    v = jnp.repeat(v.reshape(bsz, s, h_kv, hd), h // h_kv, axis=2)
    key_pos = jnp.arange(s)
    outs = []
    for q0 in range(0, s, query_block):   # in blocks of queries, so that S x S never exists
        scores = jnp.einsum("bqhd,bkhd->bhqk", q[:, q0:q0 + query_block], k) * hd ** -0.5
        seen = key_pos[None, :] <= (q0 + jnp.arange(scores.shape[2]))[:, None]
        prob = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", prob, v))
    out = jnp.concatenate(outs, axis=1).reshape(bsz, s, h * hd)
    return out @ p[pre + "o_weight"].T


def experts(p, pre, x, c, experts_held, shared=True):
    """The share of the expert layer that ``experts_held = (first, count)``
    gives, and with ``shared`` the shared expert."""
    first, count = experts_held
    s = jax.nn.sigmoid(x @ p[pre + "router_weight"].T)                   # [..., E]
    _, idx = jax.lax.top_k(s + p[pre + "select_bias"], c["num_experts_per_tok"])
    chosen = jnp.take_along_axis(s, idx, -1)
    gates = chosen * c["routed_scaling_factor"]
    if c["norm_topk_prob"]:
        gates = gates / chosen.sum(-1, keepdims=True)
    y = jnp.zeros_like(x)
    for j in range(count):
        gate = jnp.sum(jnp.where(idx == first + j, gates, 0.0), -1)      # 0: not chosen
        out = (jnp.square(jax.nn.relu(x @ p[pre + "experts_up_weight"][j]))
               @ p[pre + "experts_down_weight"][j])
        y = y + gate[..., None] * out
    if shared:
        y = y + relu2(x, p[pre + "shared_up_weight"], p[pre + "shared_down_weight"])
    return y


def forward(named_params, tok, *, config, experts_held, query_block=512, dtype=jnp.float32):
    """Logits [B, S, V] for token ids ``tok`` [B, S].  ``dtype`` float32 is
    the reference; bfloat16 computes everything in bf16 at default precision
    (the reading that the comparison's tolerances must refuse)."""
    c = config
    precision = "highest" if dtype == jnp.float32 else "default"
    with jax.default_matmul_precision(precision):
        p = {k: v.astype(dtype) for k, v in by_suffix(named_params).items()}
        x = p["model_embed_weight"][tok]
        eps = c["layer_norm_epsilon"]
        for l, kind in enumerate(c["hybrid_override_pattern"]):
            pre = f"model_layer{l}_"
            u = rms_norm(x, p[pre + "norm_gamma"], eps)
            if kind == "M":
                x = x + mamba(p, pre + "mamba_", u, c)
            elif kind == "*":
                x = x + attention(p, pre + "attn_", u, c, query_block)
            else:
                x = x + experts(p, pre + "moe_", u, c, experts_held)
        hidden = rms_norm(x, p["model_norm_gamma"], eps)
        return (hidden @ p["lm_head_weight"].T).astype(jnp.float32)


def loss_per_token(logits, labels):
    """Cross-entropy of each position against its label (the next token), [B, S]."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]


def loss(named_params, tok, labels, **kwargs):
    """Mean causal-LM loss: what ``jax.grad`` differentiates in the tests."""
    return loss_per_token(forward(named_params, tok, **kwargs), labels).mean()
