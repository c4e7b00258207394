"""Dense tensor operators — TPU-native equivalent of [U:src/operator/tensor/]
(``elemwise_binary_op*``, ``broadcast_reduce_op*``, ``matrix_op*``,
``indexing_op``, ``init_op``, ``ordering_op``).

Every op is a pure jax function; XLA fuses elementwise chains (subsuming the
reference's NVRTC pointwise fusion, [U:src/operator/fusion/]) and tiles
matmuls onto the MXU.  MXNet-specific calling conventions (reshape magic
values, ``exclude`` reduction, topk ``ret_typ``...) are honored so reference
scripts/tests port unchanged.
"""
from __future__ import annotations

import functools

import numpy as _np

import jax
import jax.numpy as jnp
from jax import lax

from ..base import _as_np_dtype
from .registry import register, alias

# ---------------------------------------------------------------------------
# broadcasting binary (MXNet names both `elemwise_*` and `broadcast_*`; jax
# broadcasts everywhere so they collapse)
# ---------------------------------------------------------------------------


@register("broadcast_add")
def broadcast_add(lhs, rhs):
    return jnp.add(lhs, rhs)


@register("broadcast_sub")
def broadcast_sub(lhs, rhs):
    return jnp.subtract(lhs, rhs)


@register("broadcast_mul")
def broadcast_mul(lhs, rhs):
    return jnp.multiply(lhs, rhs)


@register("broadcast_div")
def broadcast_div(lhs, rhs):
    return jnp.divide(lhs, rhs)


@register("broadcast_mod")
def broadcast_mod(lhs, rhs):
    return jnp.mod(lhs, rhs)


@register("broadcast_power")
def broadcast_power(lhs, rhs):
    return jnp.power(lhs, rhs)


@register("broadcast_maximum")
def broadcast_maximum(lhs, rhs):
    return jnp.maximum(lhs, rhs)


@register("broadcast_minimum")
def broadcast_minimum(lhs, rhs):
    return jnp.minimum(lhs, rhs)


@register("broadcast_hypot")
def broadcast_hypot(lhs, rhs):
    return jnp.hypot(lhs, rhs)


def _cmp_dtype(x):
    return x.dtype if hasattr(x, "dtype") else jnp.float32


@register("broadcast_equal", differentiable=False)
def broadcast_equal(lhs, rhs):
    return (jnp.equal(lhs, rhs)).astype(_cmp_dtype(lhs))


@register("broadcast_not_equal", differentiable=False)
def broadcast_not_equal(lhs, rhs):
    return (jnp.not_equal(lhs, rhs)).astype(_cmp_dtype(lhs))


@register("broadcast_greater", differentiable=False)
def broadcast_greater(lhs, rhs):
    return (jnp.greater(lhs, rhs)).astype(_cmp_dtype(lhs))


@register("broadcast_greater_equal", differentiable=False)
def broadcast_greater_equal(lhs, rhs):
    return (jnp.greater_equal(lhs, rhs)).astype(_cmp_dtype(lhs))


@register("broadcast_lesser", differentiable=False)
def broadcast_lesser(lhs, rhs):
    return (jnp.less(lhs, rhs)).astype(_cmp_dtype(lhs))


@register("broadcast_lesser_equal", differentiable=False)
def broadcast_lesser_equal(lhs, rhs):
    return (jnp.less_equal(lhs, rhs)).astype(_cmp_dtype(lhs))


@register("broadcast_logical_and", differentiable=False)
def broadcast_logical_and(lhs, rhs):
    return jnp.logical_and(lhs, rhs).astype(_cmp_dtype(lhs))


@register("broadcast_logical_or", differentiable=False)
def broadcast_logical_or(lhs, rhs):
    return jnp.logical_or(lhs, rhs).astype(_cmp_dtype(lhs))


@register("broadcast_logical_xor", differentiable=False)
def broadcast_logical_xor(lhs, rhs):
    return jnp.logical_xor(lhs, rhs).astype(_cmp_dtype(lhs))


for _new, _old in [
    ("elemwise_add", "broadcast_add"),
    ("elemwise_sub", "broadcast_sub"),
    ("elemwise_mul", "broadcast_mul"),
    ("elemwise_div", "broadcast_div"),
    ("add", "broadcast_add"),
    ("subtract", "broadcast_sub"),
    ("multiply", "broadcast_mul"),
    ("divide", "broadcast_div"),
    ("power", "broadcast_power"),
    ("maximum", "broadcast_maximum"),
    ("minimum", "broadcast_minimum"),
    ("equal", "broadcast_equal"),
    ("not_equal", "broadcast_not_equal"),
    ("greater", "broadcast_greater"),
    ("greater_equal", "broadcast_greater_equal"),
    ("lesser", "broadcast_lesser"),
    ("lesser_equal", "broadcast_lesser_equal"),
    ("logical_and", "broadcast_logical_and"),
    ("logical_or", "broadcast_logical_or"),
    ("logical_xor", "broadcast_logical_xor"),
]:
    alias(_new, _old)


# ---------------------------------------------------------------------------
# unary elementwise
# ---------------------------------------------------------------------------

_UNARY = {
    "abs": jnp.abs,
    "sign": jnp.sign,
    # MXNet rounds half AWAY from zero ([U:src/operator/tensor/
    # elemwise_unary_op_basic.cc] round); jnp.round is banker's rounding
    "round": lambda x: jnp.sign(x) * jnp.floor(jnp.abs(x) + 0.5),
    "rint": jnp.rint,
    "ceil": jnp.ceil,
    "floor": jnp.floor,
    "trunc": jnp.trunc,
    "fix": jnp.trunc,
    "exp": jnp.exp,
    "expm1": jnp.expm1,
    "log": jnp.log,
    "log10": jnp.log10,
    "log2": jnp.log2,
    "log1p": jnp.log1p,
    "sqrt": jnp.sqrt,
    "square": jnp.square,
    "cbrt": jnp.cbrt,
    "negative": jnp.negative,
    "sin": jnp.sin,
    "cos": jnp.cos,
    "tan": jnp.tan,
    "arcsin": jnp.arcsin,
    "arccos": jnp.arccos,
    "arctan": jnp.arctan,
    "sinh": jnp.sinh,
    "cosh": jnp.cosh,
    "tanh": jnp.tanh,
    "arcsinh": jnp.arcsinh,
    "arccosh": jnp.arccosh,
    "arctanh": jnp.arctanh,
    "degrees": jnp.degrees,
    "radians": jnp.radians,
    "erf": jax.scipy.special.erf,
    "erfinv": jax.scipy.special.erfinv,
    "gammaln": jax.scipy.special.gammaln,
    "logical_not": lambda x: jnp.logical_not(x).astype(x.dtype),
    "isnan": lambda x: jnp.isnan(x).astype(jnp.bool_),
    "isinf": lambda x: jnp.isinf(x).astype(jnp.bool_),
    "isfinite": lambda x: jnp.isfinite(x).astype(jnp.bool_),
}

for _name, _fn in _UNARY.items():
    register(_name)(_fn)


@register("gamma")
def gamma_fn(x):
    """Γ(x) (MXNet ``gamma`` is the gamma *function*, distinct from
    ``gammaln``)."""
    return jax.scipy.special.gamma(x)


@register("digamma")
def digamma_fn(x):
    """ψ(x) = d/dx ln Γ(x) ([U:src/operator/mshadow_op.h] gamma digamma
    family)."""
    return jax.scipy.special.digamma(x)


@register("polygamma")
def polygamma_fn(x, n=0):
    """n-th derivative of digamma ([U:src/operator/mshadow_op.h]); n=0 is
    digamma itself."""
    return jax.scipy.special.polygamma(int(n), x)


@register("reciprocal")
def reciprocal(x):
    return 1.0 / x


@register("rsqrt")
def rsqrt(x):
    return lax.rsqrt(x)


@register("rcbrt")
def rcbrt(x):
    return 1.0 / jnp.cbrt(x)


@register("relu")
def relu(x):
    return jnp.maximum(x, 0)


@register("sigmoid")
def sigmoid(x):
    return jax.nn.sigmoid(x)


@register("softsign")
def softsign(x):
    return x / (1 + jnp.abs(x))


@register("clip")
def clip(x, a_min=None, a_max=None):
    return jnp.clip(x, a_min, a_max)


@register("cast")
def cast(x, dtype):
    return x.astype(_as_np_dtype(dtype))


alias("Cast", "cast")


# ---------------------------------------------------------------------------
# reductions (MXNet semantics: axis int|tuple|None, keepdims, exclude)
# ---------------------------------------------------------------------------


def _norm_axis(x, axis, exclude=False):
    if axis is None:
        return None
    if isinstance(axis, int):
        axis = (axis,)
    axis = tuple(a % x.ndim for a in axis)
    if exclude:
        axis = tuple(i for i in range(x.ndim) if i not in axis)
    return axis


def _make_reduce(name, jfn):
    def red(x, axis=None, keepdims=False, exclude=False):
        ax = _norm_axis(x, axis, exclude)
        return jfn(x, axis=ax, keepdims=keepdims)

    red.__name__ = name
    register(name)(red)
    return red


_make_reduce("sum", jnp.sum)
_make_reduce("mean", jnp.mean)
_make_reduce("prod", jnp.prod)
_make_reduce("max", jnp.max)
_make_reduce("min", jnp.min)
_make_reduce("nansum", jnp.nansum)
_make_reduce("nanprod", jnp.nanprod)
alias("sum_axis", "sum")
alias("max_axis", "max")
alias("min_axis", "min")


@register("argmax", differentiable=False)
def argmax(x, axis=None, keepdims=False):
    out = jnp.argmax(x, axis=axis, keepdims=keepdims).astype(jnp.float32)
    return out


@register("argmin", differentiable=False)
def argmin(x, axis=None, keepdims=False):
    return jnp.argmin(x, axis=axis, keepdims=keepdims).astype(jnp.float32)


@register("norm")
def norm(x, ord=2, axis=None, keepdims=False):
    ax = _norm_axis(x, axis)
    if ord == 1:
        return jnp.sum(jnp.abs(x), axis=ax, keepdims=keepdims)
    if ord == 2:
        return jnp.sqrt(jnp.sum(jnp.square(x), axis=ax, keepdims=keepdims))
    raise ValueError(f"unsupported ord {ord}")


@register("L2Normalization")
def l2_normalization(x, eps=1e-10, mode="instance"):
    if mode == "instance":
        ax = tuple(range(1, x.ndim))
    elif mode == "channel":
        ax = (1,)
    elif mode == "spatial":
        ax = tuple(range(2, x.ndim))
    else:
        raise ValueError(mode)
    n = jnp.sqrt(jnp.sum(jnp.square(x), axis=ax, keepdims=True) + eps)
    return x / n


# ---------------------------------------------------------------------------
# matrix / shape ops
# ---------------------------------------------------------------------------


@register("dot")
def dot(lhs, rhs, transpose_a=False, transpose_b=False):
    """MXNet dot: contract last axis of lhs with first axis of rhs
    (parity: [U:src/operator/tensor/dot-inl.h]).  Lowered to an MXU matmul
    by XLA via tensordot/dot_general."""
    if transpose_a:
        lhs = jnp.transpose(lhs)
    if transpose_b:
        rhs = jnp.transpose(rhs)
    if lhs.ndim == 1 and rhs.ndim == 1:
        return jnp.dot(lhs, rhs)
    return jnp.tensordot(lhs, rhs, axes=([-1], [0]))


@register("matmul")
def matmul(lhs, rhs):
    return jnp.matmul(lhs, rhs)


@register("batch_dot")
def batch_dot(lhs, rhs, transpose_a=False, transpose_b=False):
    if transpose_a:
        lhs = jnp.swapaxes(lhs, -1, -2)
    if transpose_b:
        rhs = jnp.swapaxes(rhs, -1, -2)
    return jnp.matmul(lhs, rhs)


def _infer_mx_reshape(src, target, reverse=False):
    """MXNet reshape magic values 0/-1/-2/-3/-4
    (parity: [U:src/operator/tensor/matrix_op.cc] Reshape)."""
    src = list(src)
    target = list(target)
    if reverse:
        src = src[::-1]
        target = target[::-1]
        # -4's two factors read left-to-right; reversing swaps them back below
    out = []
    i = 0
    j = 0
    while j < len(target):
        t = target[j]
        if t > 0:
            out.append(t)
            i += 1
        elif t == 0:
            if i >= len(src):
                raise ValueError("reshape 0 refers past input rank")
            out.append(src[i])
            i += 1
        elif t == -1:
            out.append(-1)
            i += 1
        elif t == -2:
            out.extend(src[i:])
            i = len(src)
        elif t == -3:
            if i + 1 >= len(src):
                raise ValueError("reshape -3 needs two input dims")
            out.append(src[i] * src[i + 1])
            i += 2
        elif t == -4:
            d = src[i]
            a, b = target[j + 1], target[j + 2]
            if a == -1 and b == -1:
                raise ValueError("reshape -4 with two -1s")
            if a == -1:
                a = d // b
            if b == -1:
                b = d // a
            if a * b != d:
                raise ValueError(f"reshape -4 split {d} != {a}*{b}")
            out.extend([a, b])
            i += 1
            j += 2
        else:
            raise ValueError(f"invalid reshape code {t}")
        j += 1
    total = 1
    for d in src:
        total *= d
    known = 1
    neg = 0
    for d in out:
        if d == -1:
            neg += 1
        else:
            known *= d
    if neg > 1:
        raise ValueError("more than one -1 in reshape")
    if neg == 1:
        out = [total // known if d == -1 else d for d in out]
    if reverse:
        out = out[::-1]
    return tuple(out)


@register("reshape")
def reshape(x, shape, reverse=False):
    return jnp.reshape(x, _infer_mx_reshape(x.shape, shape, reverse))


alias("Reshape", "reshape")


@register("reshape_like")
def reshape_like(lhs, rhs):
    return jnp.reshape(lhs, rhs.shape)


@register("flatten")
def flatten(x):
    """Flatten to 2D keeping batch dim (parity: MXNet Flatten)."""
    if x.ndim == 0:
        return jnp.reshape(x, (1, 1))
    lead = x.shape[0]
    return jnp.reshape(x, (lead, -1))


alias("Flatten", "flatten")


@register("transpose")
def transpose(x, axes=None):
    if axes is not None and len(axes) == 0:
        axes = None
    return jnp.transpose(x, axes=axes)


@register("swapaxes")
def swapaxes(x, dim1=0, dim2=0):
    return jnp.swapaxes(x, dim1, dim2)


alias("SwapAxis", "swapaxes")


@register("expand_dims")
def expand_dims(x, axis):
    return jnp.expand_dims(x, axis)


@register("squeeze")
def squeeze(x, axis=None):
    return jnp.squeeze(x, axis=axis)


@register("broadcast_to")
def broadcast_to(x, shape):
    # MXNet allows 0 meaning "keep this dim"
    shape = tuple(s if s != 0 else x.shape[i] for i, s in enumerate(shape))
    return jnp.broadcast_to(x, shape)


@register("broadcast_like")
def broadcast_like(lhs, rhs):
    return jnp.broadcast_to(lhs, rhs.shape)


@register("broadcast_axis")
def broadcast_axis(x, axis=(), size=()):
    if isinstance(axis, int):
        axis = (axis,)
    if isinstance(size, int):
        size = (size,)
    shape = list(x.shape)
    for a, s in zip(axis, size):
        shape[a] = s
    return jnp.broadcast_to(x, tuple(shape))


@register("tile")
def tile(x, reps):
    return jnp.tile(x, reps)


@register("repeat")
def repeat(x, repeats, axis=None):
    return jnp.repeat(x, repeats, axis=axis)


@register("flip")
def flip(x, axis):
    return jnp.flip(x, axis=axis)


alias("reverse", "flip")


@register("pad")
def pad(x, mode="constant", pad_width=(), constant_value=0.0):
    """Parity: [U:src/operator/pad.cc] — pad_width is the flat MXNet tuple
    (before/after per axis)."""
    pw = [(pad_width[2 * i], pad_width[2 * i + 1]) for i in range(len(pad_width) // 2)]
    jmode = {"constant": "constant", "edge": "edge", "reflect": "reflect"}[mode]
    if jmode == "constant":
        return jnp.pad(x, pw, mode="constant", constant_values=constant_value)
    return jnp.pad(x, pw, mode=jmode)


@register("concat")
def concat(*args, dim=1):
    return jnp.concatenate(args, axis=dim)


alias("Concat", "concat")


@register("stack")
def stack(*args, axis=0):
    return jnp.stack(args, axis=axis)


@register("add_n")
def add_n(*args):
    out = args[0]
    for a in args[1:]:
        out = out + a
    return out


alias("ElementWiseSum", "add_n")


@register("split")
def split(x, num_outputs, axis=1, squeeze_axis=False):
    parts = jnp.split(x, num_outputs, axis=axis)
    if squeeze_axis:
        parts = [jnp.squeeze(p, axis=axis) for p in parts]
    return tuple(parts)


alias("SliceChannel", "split")


@register("slice")
def slice_op(x, begin, end, step=None):
    slices = []
    step = step or [None] * len(begin)
    for b, e, s in zip(begin, end, step):
        slices.append(slice(b, e, s))
    return x[tuple(slices)]


@register("slice_axis")
def slice_axis(x, axis, begin, end):
    sl = [slice(None)] * x.ndim
    if end is None:
        end = x.shape[axis]
    sl[axis] = slice(begin, end)
    return x[tuple(sl)]


@register("slice_like")
def slice_like(x, like, axes=()):
    axes = axes or tuple(range(min(x.ndim, like.ndim)))
    sl = [slice(None)] * x.ndim
    for a in axes:
        sl[a] = slice(0, like.shape[a])
    return x[tuple(sl)]


@register("take")
def take(x, indices, axis=0, mode="clip"):
    idx = indices.astype(jnp.int32)
    if mode == "wrap":
        idx = jnp.mod(idx, x.shape[axis])
    else:
        idx = jnp.clip(idx, 0, x.shape[axis] - 1)
    return jnp.take(x, idx, axis=axis)


@register("batch_take")
def batch_take(x, indices):
    idx = indices.astype(jnp.int32).reshape(-1)
    return x[jnp.arange(x.shape[0]), idx]


@register("pick")
def pick(x, index, axis=-1, keepdims=False, mode="clip"):
    idx = index.astype(jnp.int32)
    idx = jnp.clip(idx, 0, x.shape[axis] - 1)
    out = jnp.take_along_axis(x, jnp.expand_dims(idx, axis=axis), axis=axis)
    if not keepdims:
        out = jnp.squeeze(out, axis=axis)
    return out


@register("gather_nd")
def gather_nd(data, indices):
    """Parity: MXNet gather_nd — indices shape (M, ...) where leading dim
    indexes the first M axes of data."""
    idx = indices.astype(jnp.int32)
    m = idx.shape[0]
    return data[tuple(idx[i] for i in range(m))]


@register("scatter_nd")
def scatter_nd(data, indices, shape):
    idx = indices.astype(jnp.int32)
    m = idx.shape[0]
    out = jnp.zeros(shape, dtype=data.dtype)
    return out.at[tuple(idx[i] for i in range(m))].set(data)


@register("where")
def where(condition, x, y):
    return jnp.where(condition.astype(jnp.bool_) if condition.dtype != jnp.bool_ else condition, x, y)


@register("one_hot", differentiable=False)
def one_hot(indices, depth, on_value=1.0, off_value=0.0, dtype="float32"):
    idx = indices.astype(jnp.int32)
    oh = jax.nn.one_hot(idx, depth)
    out = oh * on_value + (1 - oh) * off_value
    return out.astype(_as_np_dtype(dtype))


@register("diag")
def diag(x, k=0):
    if x.ndim == 1:
        return jnp.diag(x, k)
    return jnp.diagonal(x, offset=k, axis1=-2, axis2=-1)


@register("shape_array", differentiable=False)
def shape_array(x):
    return jnp.asarray(x.shape, dtype=jnp.int64 if False else jnp.int32)


@register("size_array", differentiable=False)
def size_array(x):
    return jnp.asarray([x.size], dtype=jnp.int32)


@register("zeros_like")
def zeros_like(x):
    return jnp.zeros_like(x)


@register("ones_like")
def ones_like(x):
    return jnp.ones_like(x)


@register("full_like")
def full_like(x, fill_value=0.0):
    return jnp.full_like(x, fill_value)


# ---------------------------------------------------------------------------
# ordering ops
# ---------------------------------------------------------------------------


@register("sort")
def sort(x, axis=-1, is_ascend=True):
    out = jnp.sort(x, axis=axis)
    if not is_ascend:
        out = jnp.flip(out, axis=axis)
    return out


@register("argsort", differentiable=False)
def argsort(x, axis=-1, is_ascend=True, dtype="float32"):
    out = jnp.argsort(x, axis=axis)
    if not is_ascend:
        out = jnp.flip(out, axis=axis)
    return out.astype(_as_np_dtype(dtype))


@register("topk", differentiable=False)
def topk(x, axis=-1, k=1, ret_typ="indices", is_ascend=False, dtype="float32"):
    """Parity: [U:src/operator/tensor/ordering_op.cc] topk."""
    ax = axis % x.ndim
    xt = jnp.moveaxis(x, ax, -1)
    vals, idx = lax.top_k(jnp.negative(xt) if is_ascend else xt, k)
    if is_ascend:
        vals = jnp.negative(vals)
    vals = jnp.moveaxis(vals, -1, ax)
    idx = jnp.moveaxis(idx, -1, ax)
    if ret_typ == "indices":
        return idx.astype(_as_np_dtype(dtype))
    if ret_typ == "value":
        return vals
    if ret_typ == "both":
        return vals, idx.astype(_as_np_dtype(dtype))
    if ret_typ == "mask":
        mask = jnp.zeros_like(jnp.moveaxis(x, ax, -1))
        mask = jax.vmap(lambda m, i: m.at[i].set(1.0), in_axes=(0, 0))(
            mask.reshape(-1, mask.shape[-1]), idx.reshape(-1, idx.shape[-1] if idx.ndim else 1)
        ).reshape(mask.shape)
        return jnp.moveaxis(mask, -1, ax)
    raise ValueError(ret_typ)


# ---------------------------------------------------------------------------
# misc
# ---------------------------------------------------------------------------


@register("identity")
def identity(x):
    return x


@register("BlockGrad")
def block_grad(x):
    return lax.stop_gradient(x)


alias("stop_gradient", "BlockGrad")
alias("make_loss", "identity")


@register("smooth_l1")
def smooth_l1(x, scalar=1.0):
    s2 = scalar * scalar
    return jnp.where(jnp.abs(x) < 1.0 / s2, 0.5 * s2 * jnp.square(x), jnp.abs(x) - 0.5 / s2)


@register("hard_sigmoid")
def hard_sigmoid(x, alpha=0.2, beta=0.5):
    return jnp.clip(alpha * x + beta, 0.0, 1.0)


@register("linalg_gemm2")
def linalg_gemm2(a, b, transpose_a=False, transpose_b=False, alpha=1.0):
    if transpose_a:
        a = jnp.swapaxes(a, -1, -2)
    if transpose_b:
        b = jnp.swapaxes(b, -1, -2)
    return alpha * jnp.matmul(a, b)


@register("linalg_potrf")
def linalg_potrf(a):
    return jnp.linalg.cholesky(a)


@register("linalg_syrk")
def linalg_syrk(a, transpose=False, alpha=1.0):
    if transpose:
        return alpha * jnp.matmul(jnp.swapaxes(a, -1, -2), a)
    return alpha * jnp.matmul(a, jnp.swapaxes(a, -1, -2))


_np  # keep import


# ---------------------------------------------------------------------------
# Scalar-operand arithmetic ops (parity: [U:src/operator/tensor/
# elemwise_binary_scalar_op_basic.cc]).  NDArray dunders compute these
# directly; they are registered so the symbolic front end (mx.sym) can emit
# them as graph nodes.
# ---------------------------------------------------------------------------


@register("_plus_scalar")
def _plus_scalar(data, scalar=0.0):
    return data + data.dtype.type(scalar)


@register("_minus_scalar")
def _minus_scalar(data, scalar=0.0):
    return data - data.dtype.type(scalar)


@register("_rminus_scalar")
def _rminus_scalar(data, scalar=0.0):
    return data.dtype.type(scalar) - data


@register("_mul_scalar")
def _mul_scalar(data, scalar=1.0):
    return data * data.dtype.type(scalar)


@register("_div_scalar")
def _div_scalar(data, scalar=1.0):
    return data / data.dtype.type(scalar)


@register("_rdiv_scalar")
def _rdiv_scalar(data, scalar=1.0):
    return data.dtype.type(scalar) / data


@register("_power_scalar")
def _power_scalar(data, scalar=1.0):
    return data ** data.dtype.type(scalar)


@register("_rpower_scalar")
def _rpower_scalar(data, scalar=1.0):
    return data.dtype.type(scalar) ** data


@register("split_v2")
def split_v2(data, indices_or_sections=1, axis=0, squeeze_axis=False):
    """Parity: [U:src/operator/tensor/matrix_op.cc] _split_v2 — int = N
    equal sections, tuple = split points along ``axis``."""
    spec = (int(indices_or_sections) if isinstance(indices_or_sections, int)
            else [int(i) for i in indices_or_sections])
    if not isinstance(spec, int):
        # the reference rejects out-of-range/unsorted indices at shape
        # inference; jnp.split would silently clamp to empty parts
        if any(i < 0 or i > data.shape[axis] for i in spec) \
                or sorted(spec) != spec:
            raise ValueError(
                f"split_v2 indices {spec} invalid for axis {axis} of "
                f"size {data.shape[axis]} (must be sorted, in range)")
    parts = jnp.split(data, spec, axis=axis)
    if squeeze_axis:
        parts = [jnp.squeeze(p, axis=axis) for p in parts]
    return tuple(parts)  # tuple = multi-output contract (a list would stack)


@register("_mod_scalar")
def _mod_scalar(data, scalar=1.0):
    return jnp.mod(data, data.dtype.type(scalar))


@register("_rmod_scalar")
def _rmod_scalar(data, scalar=1.0):
    return jnp.mod(data.dtype.type(scalar), data)


@register("_maximum_scalar")
def _maximum_scalar(data, scalar=0.0):
    return jnp.maximum(data, data.dtype.type(scalar))


@register("_minimum_scalar")
def _minimum_scalar(data, scalar=0.0):
    return jnp.minimum(data, data.dtype.type(scalar))


@register("_hypot_scalar")
def _hypot_scalar(data, scalar=0.0):
    return jnp.hypot(data, data.dtype.type(scalar))


# comparisons return 1.0/0.0 in the INPUT dtype ([U:src/operator/tensor/
# elemwise_binary_scalar_op_logic.cc] — the reference's float-mask
# convention, not bool arrays)
def _make_cmp_scalar(name, fn):
    @register(name, differentiable=False)
    def cmp_scalar(data, scalar=0.0, _fn=fn):
        return _fn(data, data.dtype.type(scalar)).astype(data.dtype)

    cmp_scalar.__name__ = name.lstrip("_")
    return cmp_scalar


for _name, _fn in [
    ("_equal_scalar", jnp.equal),
    ("_not_equal_scalar", jnp.not_equal),
    ("_greater_scalar", jnp.greater),
    ("_greater_equal_scalar", jnp.greater_equal),
    ("_lesser_scalar", jnp.less),
    ("_lesser_equal_scalar", jnp.less_equal),
    ("_logical_and_scalar", jnp.logical_and),
    ("_logical_or_scalar", jnp.logical_or),
    ("_logical_xor_scalar", jnp.logical_xor),
]:
    _make_cmp_scalar(_name, _fn)


@register("_sym_zeros")
def _sym_zeros(shape=(), dtype="float32"):
    return jnp.zeros(tuple(shape), dtype=_as_np_dtype(dtype))


@register("_sym_ones")
def _sym_ones(shape=(), dtype="float32"):
    return jnp.ones(tuple(shape), dtype=_as_np_dtype(dtype))


alias("broadcast_axes", "broadcast_axis")
alias("crop", "slice")  # [U:src/operator/tensor/matrix_op.cc] add_alias("crop")


# ---------------------------------------------------------------------------
# legacy ndarray functions (parity: [U:src/ndarray/ndarray_function.cc] —
# the pre-Gluon RL/embedding-era API; choose_element_0index is the old
# name for pick along axis 1)
# ---------------------------------------------------------------------------


@register("choose_element_0index")
def choose_element_0index(lhs, rhs):
    """out[i] = lhs[i, rhs[i]] — the old name for pick along axis 1."""
    return pick(lhs, rhs, axis=1)


@register("fill_element_0index")
def fill_element_0index(lhs, mhs, rhs):
    """out = lhs with out[i, rhs[i]] = mhs[i] (functional, not in-place —
    the buffer-swap NDArray layer applies the mutation)."""
    idx = jnp.clip(rhs.astype(jnp.int32), 0, lhs.shape[1] - 1)
    return lhs.at[jnp.arange(lhs.shape[0]), idx].set(mhs)


@register("one_hot_encode")
def one_hot_encode(indices, out):
    """Legacy 2-arg form: the second operand supplies the [N, C] shape."""
    idx = indices.astype(jnp.int32)
    return jax.nn.one_hot(idx, out.shape[1], dtype=out.dtype)


# ---------------------------------------------------------------------------
# AMP graph-pass ops (parity: [U:src/operator/tensor/amp_cast.cc]) — the
# reference inserts these around float ops during the AMP symbol pass;
# they exist here so reference-era symbol graphs execute unchanged
# ---------------------------------------------------------------------------


@register("amp_cast")
def amp_cast(x, dtype="float32"):
    """Cast floating inputs; pass integer/bool tensors through unchanged
    (the reference op's contract)."""
    if not jnp.issubdtype(x.dtype, jnp.floating):
        return x
    return x.astype(_as_np_dtype(dtype))


@register("amp_multicast")
def amp_multicast(*data, num_outputs=0, cast_narrow=False):
    """Cast every floating operand to a common width: the widest among the
    inputs (or the narrowest with ``cast_narrow``)."""
    floats = [d.dtype for d in data if jnp.issubdtype(d.dtype, jnp.floating)]
    if not floats:
        return tuple(data)
    if cast_narrow:
        # deterministic tie-break (f16 vs bf16): sort by (bits, name)
        target = min(floats, key=lambda dt: (jnp.finfo(dt).bits, dt.name))
    else:
        # promote_types is order-invariant and lifts f16+bf16 to f32
        target = functools.reduce(jnp.promote_types, floats)
    return tuple(d.astype(target)
                 if jnp.issubdtype(d.dtype, jnp.floating) else d
                 for d in data)


@register("_contrib_boolean_mask")
def boolean_mask(data, index, axis=0):
    """Select the slices of ``data`` along ``axis`` where ``index`` is
    nonzero (parity: [U:src/operator/contrib/boolean_mask.cc]).  The
    output length depends on the MASK's values, so the mask must be
    concrete: with a concrete mask the op lowers to ``take`` over the
    precomputed indices (static shape, differentiable — the autograd tape
    keeps no-grad inputs concrete, so ``data`` may be traced); a traced
    mask raises with guidance."""
    import jax.core as _core

    if isinstance(index, _core.Tracer):
        raise NotImplementedError(
            "boolean_mask needs a CONCRETE mask (its output length is the "
            "mask's popcount); inside jit use jnp.where-style masked "
            "compute or mask-and-pad instead")
    mask = _np.asarray(index)
    if mask.ndim != 1 or mask.shape[0] != data.shape[axis]:
        raise ValueError(
            f"boolean_mask: mask shape {mask.shape} must be 1-D of length "
            f"data.shape[{axis}]={data.shape[axis]}")
    idx = _np.nonzero(mask != 0)[0]
    return jnp.take(data, idx, axis=axis)
