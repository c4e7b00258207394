"""Transformer layers (Gluon authoring style).

The reference keeps transformer blocks out-of-repo (GluonNLP); they are
in-repo here because BERT-base and Transformer-big are two of the five
baseline workloads (BASELINE.md).  Layers follow the reference's Gluon
conventions — ``hybrid_forward(F, ..., **params)``, deferred shapes via
``_shape_inference`` — so they hybridize/jit and shard like every other
block.  The attention core is :func:`ops.attention.flash_attention`
(Pallas on TPU); head projections are single fused matmuls (MXU-friendly:
one [B·S, D]×[D, 3D] GEMM for self-attention QKV).

TP sharding conventions (used by parallel.ShardingRules in the models):
qkv/ffn-in weights shard over 'tp' on the output dim (column-parallel),
out-proj/ffn-out over the input dim (row-parallel).
"""
from __future__ import annotations

import math

import numpy as _np

from ..block import HybridBlock
from .basic_layers import Dense, Dropout, LayerNorm, Embedding

__all__ = [
    "MultiHeadAttention",
    "PositionwiseFFN",
    "TransformerEncoderCell",
    "TransformerEncoder",
    "TransformerDecoderCell",
    "TransformerDecoder",
    "PositionalEmbedding",
    "SinusoidalPositionalEncoding",
]


class MultiHeadAttention(HybridBlock):
    """Multi-head attention with fused QKV projection and flash-attention
    core.  Inputs [B, S, D]; optional [B, S_kv, D] memory for cross-attn."""

    def __init__(self, units, num_heads, dropout=0.0, causal=False, use_bias=True,
                 cross=False, dtype="float32", prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        if units % num_heads:
            raise ValueError(f"units {units} not divisible by num_heads {num_heads}")
        self._units = units
        self._num_heads = num_heads
        self._causal = causal
        self._cross = cross
        with self.name_scope():
            if cross:
                self.q_proj = Dense(units, use_bias=use_bias, flatten=False, dtype=dtype, prefix="q_")
                self.kv_proj = Dense(2 * units, use_bias=use_bias, flatten=False, dtype=dtype, prefix="kv_")
            else:
                self.qkv = Dense(3 * units, use_bias=use_bias, flatten=False, dtype=dtype, prefix="qkv_")
            self.out_proj = Dense(units, use_bias=use_bias, flatten=False, dtype=dtype, prefix="out_")
        self._dropout = Dropout(dropout) if dropout else None
        if self._dropout is not None:
            self.register_child(self._dropout, "dropout")

    def forward(self, x, memory=None):
        F = self._F
        H = self._num_heads
        if self._cross:
            if memory is None:
                raise ValueError("cross-attention requires a memory input")
            q = self.q_proj(x)
            kv = self.kv_proj(memory)
            out = F.contrib.fused_kv_attention(q, kv, num_heads=H, causal=self._causal)
        else:
            qkv = self.qkv(x)  # [B, S, 3D]
            out = F.contrib.fused_qkv_attention(qkv, num_heads=H, causal=self._causal)
        out = self.out_proj(out)
        if self._dropout is not None:
            out = self._dropout(out)
        return out

    @property
    def _F(self):
        from ... import ndarray as nd_mod

        return nd_mod

    def __repr__(self):
        return f"MultiHeadAttention(units={self._units}, heads={self._num_heads}, causal={self._causal})"


class PositionwiseFFN(HybridBlock):
    """FFN sublayer: Dense→act→(dropout)→Dense (one MXU GEMM each)."""

    def __init__(self, units, hidden_size, activation="gelu", dropout=0.0,
                 dtype="float32", prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.ffn_1 = Dense(hidden_size, flatten=False, dtype=dtype, prefix="ffn1_")
            self.ffn_2 = Dense(units, flatten=False, dtype=dtype, prefix="ffn2_")
        self._activation = activation
        self._dropout = Dropout(dropout) if dropout else None
        if self._dropout is not None:
            self.register_child(self._dropout, "dropout")

    def forward(self, x):
        from ... import ndarray as F

        h = self.ffn_1(x)
        if self._activation == "gelu":
            h = F.LeakyReLU(h, act_type="gelu")
        else:
            h = F.Activation(h, act_type=self._activation)
        if self._dropout is not None:
            h = self._dropout(h)
        return self.ffn_2(h)


class TransformerEncoderCell(HybridBlock):
    """Pre/post-LN encoder layer (post-LN default = BERT convention)."""

    def __init__(self, units, hidden_size, num_heads, dropout=0.0,
                 pre_norm=False, activation="gelu", dtype="float32",
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._pre_norm = pre_norm
        with self.name_scope():
            # sublayer (residual) dropout is applied ONCE by this cell via
            # self._drop — the wrapped blocks get dropout=0 to avoid
            # double-dropping the same tensor.
            self.attention = MultiHeadAttention(units, num_heads, dropout=0.0, dtype=dtype, prefix="attn_")
            self.ln_attn = LayerNorm(prefix="ln1_")
            self.ffn = PositionwiseFFN(units, hidden_size, activation, dropout=0.0, dtype=dtype, prefix="ffn_")
            self.ln_ffn = LayerNorm(prefix="ln2_")
        self._drop = Dropout(dropout) if dropout else None
        if self._drop is not None:
            self.register_child(self._drop, "dropout")

    def forward(self, x):
        if self._pre_norm:
            h = self.attention(self.ln_attn(x))
            x = x + (self._drop(h) if self._drop else h)
            h = self.ffn(self.ln_ffn(x))
            return x + (self._drop(h) if self._drop else h)
        h = self.attention(x)
        x = self.ln_attn(x + (self._drop(h) if self._drop else h))
        h = self.ffn(x)
        return self.ln_ffn(x + (self._drop(h) if self._drop else h))


class TransformerEncoder(HybridBlock):
    def __init__(self, num_layers, units, hidden_size, num_heads, dropout=0.0,
                 pre_norm=False, activation="gelu", dtype="float32",
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._layers = []
        with self.name_scope():
            for i in range(num_layers):
                cell = TransformerEncoderCell(
                    units, hidden_size, num_heads, dropout, pre_norm, activation,
                    dtype=dtype, prefix=f"layer{i}_",
                )
                self.register_child(cell, f"layer{i}")
                self._layers.append(cell)

    def forward(self, x):
        for cell in self._layers:
            x = cell(x)
        return x


class TransformerDecoderCell(HybridBlock):
    """Decoder layer: causal self-attn + cross-attn + FFN."""

    def __init__(self, units, hidden_size, num_heads, dropout=0.0,
                 pre_norm=False, activation="relu", dtype="float32",
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._pre_norm = pre_norm
        with self.name_scope():
            # same single-residual-dropout discipline as the encoder cell
            self.self_attention = MultiHeadAttention(
                units, num_heads, dropout=0.0, causal=True, dtype=dtype, prefix="selfattn_"
            )
            self.ln_self = LayerNorm(prefix="ln1_")
            self.cross_attention = MultiHeadAttention(
                units, num_heads, dropout=0.0, cross=True, dtype=dtype, prefix="crossattn_"
            )
            self.ln_cross = LayerNorm(prefix="ln2_")
            self.ffn = PositionwiseFFN(units, hidden_size, activation, dropout=0.0, dtype=dtype, prefix="ffn_")
            self.ln_ffn = LayerNorm(prefix="ln3_")
        self._drop = Dropout(dropout) if dropout else None
        if self._drop is not None:
            self.register_child(self._drop, "dropout")

    def forward(self, x, memory):
        d = self._drop if self._drop is not None else (lambda t: t)
        if self._pre_norm:
            x = x + d(self.self_attention(self.ln_self(x)))
            x = x + d(self.cross_attention(self.ln_cross(x), memory))
            return x + d(self.ffn(self.ln_ffn(x)))
        x = self.ln_self(x + d(self.self_attention(x)))
        x = self.ln_cross(x + d(self.cross_attention(x, memory)))
        return self.ln_ffn(x + d(self.ffn(x)))


class TransformerDecoder(HybridBlock):
    def __init__(self, num_layers, units, hidden_size, num_heads, dropout=0.0,
                 pre_norm=False, activation="relu", dtype="float32",
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._layers = []
        with self.name_scope():
            for i in range(num_layers):
                cell = TransformerDecoderCell(
                    units, hidden_size, num_heads, dropout, pre_norm, activation,
                    dtype=dtype, prefix=f"layer{i}_",
                )
                self.register_child(cell, f"layer{i}")
                self._layers.append(cell)

    def forward(self, x, memory):
        for cell in self._layers:
            x = cell(x, memory)
        return x


class PositionalEmbedding(HybridBlock):
    """Learned positional embedding (BERT style)."""

    def __init__(self, max_length, units, dtype="float32", prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._max_length = max_length
        with self.name_scope():
            self.embed = Embedding(max_length, units, dtype=dtype, prefix="pos_")

    def forward(self, x):
        """x: [B, S, D] → x + pos[:S]."""
        from ... import ndarray as F

        positions = F.arange(0, x.shape[1], dtype="int32")
        return x + self.embed(positions)


class SinusoidalPositionalEncoding(HybridBlock):
    """Fixed sinusoidal encoding (Transformer-WMT style); no parameters."""

    def __init__(self, units, max_length=4096, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._units = units
        pos = _np.arange(max_length)[:, None]
        dim = _np.arange((units + 1) // 2)[None, :]
        angle = pos / _np.power(10000.0, 2 * dim / units)
        table = _np.zeros((max_length, units), dtype=_np.float32)
        table[:, 0::2] = _np.sin(angle)
        table[:, 1::2] = _np.cos(angle[:, : units // 2])
        self._table = table

    def forward(self, x):
        import jax.numpy as jnp

        from ...ndarray.ndarray import NDArray

        seq = x.shape[1]
        table = jnp.asarray(self._table[:seq]).astype(x.dtype)  # no bf16→f32 promotion
        return x + NDArray(table)
