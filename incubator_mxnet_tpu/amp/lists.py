"""AMP op lists (parity: [U:python/mxnet/contrib/amp/lists/symbol_fp16.py]).

Three tiers, consulted by the dispatch hook in ndarray.invoke:
* TARGET_OPS  — run in the low-precision target dtype (the MXU ops where
  all the FLOPs are: matmul/conv/attention); float inputs are cast down.
* FP32_OPS    — numerically sensitive; float inputs are cast UP to fp32
  (softmax/exp/norm/loss heads).
* WIDEST_OPS  — multi-input ops that must agree on a dtype; inputs are
  cast to the widest float dtype present.
Everything else passes through untouched.

bf16 is the TPU-native target (fp16's loss-scaling machinery is kept for
API parity but bf16 needs no scaler — same exponent range as fp32).
"""

TARGET_OPS = {
    "FullyConnected", "fully_connected",
    "Convolution", "Deconvolution",
    "dot", "batch_dot", "linalg_gemm2",
    "fused_attention", "fused_qkv_attention", "fused_kv_attention",
    "latent_attention", "swiglu_ffn", "relu2_ffn",
    # the whole indexer-selected attention layer: its projections ride the
    # MXU in the target dtype; index scores, the selection's comparisons,
    # soft-maxes and the indexer's loss are float32 inside it whatever arrives
    "sparse_attention",
    "RNN",
    # Embedding output feeds the transformer residual stream; emitting it
    # in the target dtype keeps that stream bf16 end-to-end (the norms
    # below preserve input dtype), killing the per-sublayer cast copies
    # the round-2 profile charged ~2-3% MFU to (docs/PERF_NOTES.md).
    "Embedding",
}

# softmax/log_softmax/softmin and the norms are NOT fp32-listed: the ops
# themselves compute exp/statistics in fp32 and return the input dtype
# (ops/nn.py), which is numerically equivalent to hook-casting but lets
# the converts fuse into the reduction instead of materializing copies.
FP32_OPS = {
    "SoftmaxOutput", "Softmax", "softmax_cross_entropy",
    "LinearRegressionOutput", "MAERegressionOutput", "LogisticRegressionOutput",
    "L2Normalization", "norm",
    "exp", "log", "log2", "log10", "log1p", "expm1",
    "sum", "mean", "prod", "nansum", "nanprod",
    "erf", "erfinv", "gamma", "gammaln",
    "smooth_l1", "MakeLoss",
    "power", "broadcast_power", "_power_scalar", "sqrt", "rsqrt", "square",
    # the lightning indexer's pieces, called alone (ops/sparse_attention.py):
    # a near-tie at the topk-th score selects another key under any rounding
    "lightning_index_scores", "indexer_kl_loss",
}

# The state-space ops (ops/ssm.py: causal_conv1d, ssm_scan, gated_rms_norm,
# mamba2_mixer) are in NO tier, like moe_ffn_dropless and the mHC mixes: they
# take their compute dtype from the activations they are handed (bf16 under
# AMP: the matrix products ride the MXU in it) and keep in float32, whatever
# arrives, the step (softplus), the decays, their cumulative sums and
# exponentials, the carried state, the convolution's sum and the gated norm's
# statistics; A_log, dt_bias and D stay float32 parameters under
# ``cast("bfloat16")``.  A cast of every input, up or down, would be wrong
# for one half of them.

WIDEST_OPS = {
    "broadcast_add", "broadcast_sub", "broadcast_mul", "broadcast_div",
    "elemwise_add", "elemwise_sub", "elemwise_mul", "elemwise_div",
    "concat", "Concat", "stack", "add_n", "where",
}
